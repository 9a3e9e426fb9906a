"""Device table -> host rows: the sink/display boundary.

Decodes dictionary ids back to strings, restores absolute timestamps from
the batch base, renders deferred string templates (CONCAT et al.), and
folds flattened struct/array columns back into nested JSON values —
producing the same row JSON the reference's sinks serialize
(OutputManager.scala:103-126 to_json(struct(cols))).

What crosses the boundary is a ``ColumnBatch``: one output's valid rows
of one batch, kept as columns. A schema of flat scalar columns is
rendered column by column (numpy), and the native encoder writes the
sinks' NDJSON from those columns without a Python object a row; any
other schema (a dotted name, a ``.__valid`` flag, a deferred template,
a host-side ORDER BY) goes through ``materialize_rows`` row by row,
behind the same type. Either way the batch is a read-only
``Sequence[dict]`` for the sinks that want rows.
"""

from __future__ import annotations

import json
from collections.abc import Sequence
from typing import Callable, Dict, List, Optional, Tuple, Union

import numpy as np

from ..compile.exprs import WS_MARKER
from ..compile.planner import TableData, ViewSchema
from ..core.schema import StringDictionary
from ..native import NdjsonBuffer, encode_ndjson


def _render_value(v, t: str, dictionary: StringDictionary, base_ms: int):
    if t == "string":
        return dictionary.decode(int(v))
    if t == "timestamp":
        return int(v) + base_ms
    if t == "tssec":
        return int(v) + base_ms // 1000
    if t == "boolean":
        return bool(v)
    if t == "double":
        return float(v)
    return int(v)


def materialize_rows(
    table: TableData,
    schema: ViewSchema,
    dictionary: StringDictionary,
    base_ms: int = 0,
    max_rows: Optional[int] = None,
) -> List[dict]:
    """Valid rows as JSON-ready dicts with nested structs re-assembled."""
    cols = {k: np.asarray(v) for k, v in table.cols.items()}
    valid = np.asarray(table.valid)
    idx = np.nonzero(valid)[0]
    if max_rows is not None:
        idx = idx[:max_rows]

    # organize flattened names into nesting groups
    device_cols = [
        c for c in schema.types if not c.startswith("__defer.")
    ]

    out: List[dict] = []
    for i in idx:
        row: dict = {}
        for c in device_cols:
            if c.endswith(".__valid"):
                continue
            v = _render_value(cols[c][i], schema.types[c], dictionary, base_ms)
            _bury(row, c, v)
        # deferred string templates. CONCAT: a NULL part nulls the
        # whole result (matching the device hash tier). CONCAT_WS
        # (WS_MARKER-tagged): null ARGUMENTS are skipped and the rest
        # join on the separator — both per Spark semantics.
        for name, parts in schema.deferred.items():
            ws_sep = None
            if parts and isinstance(parts[0], str) \
                    and parts[0].startswith(WS_MARKER):
                ws_sep = parts[0][len(WS_MARKER):]
                parts = parts[1:]
            pieces = []
            for p in parts:
                if isinstance(p, str):
                    pieces.append(p)
                    continue
                hidden, t = p
                rendered = _render_value(
                    cols[hidden][i], t, dictionary, base_ms
                )
                if rendered is None:
                    if ws_sep is not None:
                        continue  # concat_ws skips null arguments
                    pieces = None
                    break
                pieces.append(
                    f"{rendered:g}" if t == "double" else str(rendered)
                )
            if pieces is None:
                value = None
            elif ws_sep is not None:
                value = ws_sep.join(pieces)
            else:
                value = "".join(pieces)
            _bury(row, name, value)
        # array/struct validity: drop nulled-out branches
        row = _apply_validity(row, cols, schema, i)
        out.append(row)
    return out


def _apply_validity(row: dict, cols, schema: ViewSchema, i: int) -> dict:
    """Remove subtrees whose ``__valid`` flag is False; collapse arrays
    (numeric-keyed dicts) into lists of surviving elements."""
    valid_flags = {
        c[: -len(".__valid")]: bool(cols[c][i])
        for c in schema.types
        if c.endswith(".__valid")
    }

    def prune(obj, path: str):
        if not isinstance(obj, dict):
            return obj
        if path in valid_flags and not valid_flags[path]:
            return None
        keys = list(obj.keys())
        if keys and all(k.isdigit() for k in keys):
            items = []
            for k in sorted(keys, key=int):
                sub = prune(obj[k], f"{path}.{k}" if path else k)
                if sub is not None:
                    items.append(sub)
            return items
        out = {}
        for k in keys:
            sub = prune(obj[k], f"{path}.{k}" if path else k)
            if sub is not None or (f"{path}.{k}" if path else k) not in valid_flags:
                out[k] = sub
        return out

    return {k: prune(v, k) for k, v in row.items()}


def _bury(obj: dict, dotted: str, value) -> None:
    parts = dotted.split(".")
    cur = obj
    for p in parts[:-1]:
        cur = cur.setdefault(p, {})
    cur[parts[-1]] = value


# -- columnar egress --------------------------------------------------------
def _is_flat(schema: ViewSchema) -> bool:
    """Every column a top-level scalar: no nested (dotted) name, no
    ``.__valid`` flag, no ``__defer.`` part, no deferred template (and
    at least one column: rows without columns are still rows)."""
    return bool(schema.types) and not schema.deferred and not any(
        "." in c for c in schema.types
    )


class ColumnBatch(Sequence):
    """One output's valid rows of one batch: what ``collect_tables``
    hands to ``Sink.write``.

    ``columnar`` says which way the schema sent it. Columnar: the valid
    rows' columns are rendered once, as numpy columns (validity mask
    once; ``timestamp`` / ``tssec`` widened to int64 before the batch
    base is added; float32 -> float64; string ids decoded once per
    distinct id); ``ndjson()`` has the native encoder write the sinks'
    payload straight from them, and the row dicts exist only if someone
    asks. Otherwise the rows are built by ``materialize_rows`` at
    construction (then sorted and cut by ``finish``, the view's
    host-side ORDER BY / LIMIT).

    As a sequence (``len``, iteration, indexing, slicing, ``==`` with a
    list) it is the very ``List[dict]`` ``materialize_rows`` gives,
    built on first use and kept. Read-only: nobody mutates a batch.
    """

    def __init__(
        self,
        table: TableData,
        schema: ViewSchema,
        dictionary: StringDictionary,
        base_ms: int = 0,
        max_rows: Optional[int] = None,
        finish: Optional[Callable[[List[dict]], List[dict]]] = None,
    ):
        self.schema = schema
        self._rows: Optional[List[dict]] = None
        # rows the native encoder has written from this batch, over all
        # its sinks (Sink_NativeEncoded_Rows)
        self.encoded_rows = 0
        # (name, type, rendered column); a string column is
        # (distinct strings, index of each row's string among them)
        self._columns: List[Tuple[str, str, object]] = []
        self.columnar = finish is None and _is_flat(schema)
        if self.columnar:
            self.columnar = self._render(table, dictionary, base_ms, max_rows)
        if not self.columnar:
            rows = materialize_rows(
                table, schema, dictionary, base_ms, max_rows
            )
            self._rows = rows if finish is None else finish(rows)
            self._len = len(self._rows)

    def _render(self, table, dictionary, base_ms, max_rows) -> bool:
        valid = np.asarray(table.valid)
        idx = np.nonzero(valid)[0]
        if max_rows is not None:
            idx = idx[:max_rows]
        # compacted outputs arrive sliced to their count, every row valid
        take_all = len(idx) == len(valid)
        columns = []
        for name, t in self.schema.types.items():
            col = table.cols.get(name)
            if col is None or np.shape(col) != valid.shape:
                return False
            col = np.asarray(col)
            if not take_all:
                col = col[idx]
            if t == "string":
                ids, where = np.unique(col, return_inverse=True)
                col = (
                    [dictionary.decode(i) for i in ids.tolist()],
                    where.reshape(-1).astype(np.int64, copy=False),
                )
            elif t == "timestamp":
                col = col.astype(np.int64) + base_ms
            elif t == "tssec":
                col = col.astype(np.int64) + base_ms // 1000
            elif t == "boolean":
                col = col.astype(np.bool_)
            elif t == "double":
                with np.errstate(invalid="ignore"):  # a signalling NaN
                    col = col.astype(np.float64)
            elif col.dtype.kind in "iub":
                col = col.astype(np.int64)
            else:
                return False
            columns.append((name, t, col))
        self._columns = columns
        self._len = len(idx)
        return True

    def __len__(self) -> int:
        return self._len

    def __getitem__(self, i):
        return self.rows()[i]

    def __iter__(self):
        return iter(self.rows())

    def __eq__(self, other) -> bool:
        if isinstance(other, (ColumnBatch, list)):
            return self.rows() == list(other)
        return NotImplemented

    __hash__ = None

    def __repr__(self) -> str:
        return (
            f"ColumnBatch({self._len} rows of {list(self.schema.types)}, "
            f"columnar={self.columnar})"
        )

    def rows(self) -> List[dict]:
        """The rows as JSON-ready dicts (the list ``collect()`` returns)."""
        if self._rows is None:
            names = [name for name, _t, _c in self._columns]
            values = []
            for _name, t, col in self._columns:
                if t == "string":
                    strings, where = col
                    col = np.array(strings, dtype=object)[where]
                values.append(col.tolist())
            self._rows = [dict(zip(names, r)) for r in zip(*values)]
        return self._rows

    def ndjson(self, out: Optional[NdjsonBuffer] = None):
        """One JSON object a row, one row a line: byte for byte
        ``json.dumps(row, default=str) + "\n"`` over ``rows()``, as
        bytes. A columnar batch's are written by the native encoder
        straight from the columns (``encoded_rows`` counts them), into
        ``out`` when its caller keeps one: the payload is then a view
        of it, valid until that buffer's next use."""
        if not self._len:
            return b""
        if not self.columnar:
            return ndjson(self._rows)
        self.encoded_rows += self._len
        return encode_ndjson(
            self._len, [(name, col) for name, _t, col in self._columns], out
        )


def ndjson(rows: Union[ColumnBatch, Sequence], out=None):
    """The NDJSON payload of a sink write, as bytes: from the columns
    when ``rows`` is a batch (into ``out``, see ``ColumnBatch.ndjson``),
    row by row for a plain list of dicts."""
    if isinstance(rows, ColumnBatch):
        return rows.ndjson(out)
    return "".join(json.dumps(r, default=str) + "\n" for r in rows).encode()
