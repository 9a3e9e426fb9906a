"""ctypes binding for the native codec: JSON -> columns at ingest,
columns -> NDJSON at the sinks.

The C++ library (``native/decoder.cpp``) replaces the role Spark's
executor-side ``from_json`` plays in the reference
(CommonProcessorFactory.scala:90-103): every event's JSON parse happens
in native code straight into numpy buffers; and the role its
``to_json(struct(cols))`` plays at the sinks (OutputManager.scala:103-126):
a result batch's payload is written from its columns by the same
library. The shared library builds
with g++ on first use into ``native/.build/`` under a name that is a
hash of ``decoder.cpp`` and the compiler flags, so the library loaded
is always the one built from the source in this checkout — a stale or
foreign ``.so`` has a different name and is never looked at. A build
that fails raises :class:`NativeBuildError` with the compiler's stderr;
nothing on the served path decodes, or encodes a columnar batch, in
Python instead.

Three decode surfaces:

- ``decode``: newline-JSON -> per-column numpy arrays (the row layout;
  the mesh path and golden-parity tests use it);
- ``decode_packed``: newline-JSON straight into a persistent
  [n_cols+1, capacity] int32 matrix — the exact single-transfer H2D
  layout ``runtime/processor.py pack_raw`` builds, so the hot path
  performs zero per-batch column allocations and no pack copy. The
  matrices come from a :class:`PackedBufferPool` (64-byte-aligned, so
  the CPU backend's ``jnp.asarray`` transfer is zero-copy) and are
  double-buffered against the pipelined in-flight window by the
  processor (a slot is only reused after its batch lands or abandons).
  A call takes the row slots from ``slot`` on, so a batch may be
  decoded in passes as its lines arrive (the paced host's wait does:
  ``runtime/processor.py decode_ahead``) and the one-shot decode is the
  one-pass case;
- ``decode_kafka_packed``: native Kafka v2 record-batch walking
  (varint framing, CRC-32C verification, control-batch skip,
  typed rejection of compressed batches) feeding each record value to
  the same JSON column decoder in the same call — the production wire
  format never touches a Python object per record.

One encode surface:

- ``encode_ndjson``: a result batch's rendered columns (int64, float64,
  bool, or a string column as its distinct strings plus an index a row)
  -> the sinks' newline-JSON payload, byte for byte
  ``json.dumps(row) + "\\n"`` a row, into an :class:`NdjsonBuffer` its
  caller keeps from one write to the next. No Python object a row or a
  value, and the interpreter lock is released for the call, so the
  dispatcher's per-output threads overlap.

The decoder owns a string dictionary (string -> int32) kept consistent
with the Python ``StringDictionary`` by push-before/pull-after syncs
around each decode call; both sides assign ids sequentially so ids
stay stable across the boundary.

Shard count: ``datax.job.process.ingest.decoderthreads`` (plumbed via
the ``threads`` ctor arg) > ``DATAX_DECODER_THREADS`` env override >
the engine default (cap 4 — ingest shares the host with the engine
loop and sinks).
"""

from __future__ import annotations

import ctypes
import hashlib
import json
import logging
import os
import subprocess
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.schema import ColType, Schema, StringDictionary

logger = logging.getLogger(__name__)

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
    "decoder.cpp",
)
_BUILD_DIR = os.path.join(os.path.dirname(_SRC), ".build")
_CXX = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread"]
_build_lock = threading.Lock()
_lib = None
_lib_error: Optional[str] = None


class NativeBuildError(RuntimeError):
    """The native decoder could not be built from ``decoder.cpp``."""

_CTYPE_NAME = {
    ColType.LONG: "long",
    ColType.DOUBLE: "double",
    ColType.BOOLEAN: "boolean",
    ColType.STRING: "string",
    ColType.TIMESTAMP: "timestamp",
}

_NP_DTYPE = {
    ColType.LONG: np.int32,
    ColType.DOUBLE: np.float32,
    ColType.BOOLEAN: np.uint8,
    ColType.STRING: np.int32,
    ColType.TIMESTAMP: np.int64,
}

# Kafka v2 attribute codec ids (message format v2)
KAFKA_CODEC_NAMES = {1: "gzip", 2: "snappy", 3: "lz4", 4: "zstd"}

# dx_decode_kafka_packed stats vector layout (decoder.cpp KStat)
_KSTAT_RECORDS = 0
_KSTAT_MALFORMED = 1
_KSTAT_CORRUPT = 2
_KSTAT_CONTROL = 3
_KSTAT_OVERFLOW = 4
_KSTAT_CODEC = 5


def _build_library() -> str:
    """Path of the library built from THIS checkout's ``decoder.cpp``
    with ``_CXX``: ``native/.build/libdxdecoder-<hash>.so``. Compiles
    it when absent (to a temp name, renamed into place, so concurrent
    processes never load a half-written file)."""
    try:
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(
                f.read() + "\0".join(_CXX).encode()
            ).hexdigest()[:16]
    except OSError as e:
        raise NativeBuildError(f"native decoder source unreadable: {e}")
    path = os.path.join(_BUILD_DIR, f"libdxdecoder-{digest}.so")
    if os.path.exists(path):
        return path
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        done = subprocess.run(
            _CXX + ["-o", tmp, _SRC], capture_output=True, timeout=300,
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeBuildError(f"native decoder build did not run: {e}")
    if done.returncode != 0:
        raise NativeBuildError(
            f"native decoder build failed ({' '.join(_CXX)} {_SRC}, "
            f"exit {done.returncode}):\n"
            + done.stderr.decode("utf-8", "replace")
        )
    os.replace(tmp, path)
    return path


def load_library():
    """The loaded library; raises :class:`NativeBuildError` (with the
    compiler's stderr) when it cannot be built. The outcome is cached
    for the process either way."""
    global _lib, _lib_error
    if _lib is not None:
        return _lib
    with _build_lock:
        if _lib is not None:
            return _lib
        if _lib_error is not None:
            raise NativeBuildError(_lib_error)
        try:
            path = _build_library()
        except NativeBuildError as e:
            _lib_error = str(e)
            raise
        lib = ctypes.CDLL(path)
        lib.dx_decoder_create.restype = ctypes.c_void_p
        lib.dx_decoder_create.argtypes = [ctypes.c_char_p]
        lib.dx_decoder_destroy.argtypes = [ctypes.c_void_p]
        lib.dx_num_columns.restype = ctypes.c_int64
        lib.dx_num_columns.argtypes = [ctypes.c_void_p]
        lib.dx_decode.restype = ctypes.c_int64
        lib.dx_decode.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.dx_decode_mt.restype = ctypes.c_int64
        lib.dx_decode_mt.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
        ]
        lib.dx_decode_packed.restype = ctypes.c_int64
        lib.dx_decode_packed.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
        ]
        lib.dx_decode_kafka_packed.restype = ctypes.c_int64
        lib.dx_decode_kafka_packed.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
        ]
        lib.dx_crc32c.restype = ctypes.c_uint32
        lib.dx_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.dx_scan_lines.restype = ctypes.c_int64
        lib.dx_scan_lines.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.dx_encode_ndjson.restype = ctypes.c_int64
        lib.dx_encode_ndjson.argtypes = [
            ctypes.c_int64, ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_void_p, ctypes.c_int64,
        ]
        lib.dx_bad_timestamps.restype = ctypes.c_int64
        lib.dx_bad_timestamps.argtypes = [ctypes.c_void_p]
        lib.dx_packed_shard_bytes.restype = ctypes.c_int64
        lib.dx_packed_shard_bytes.argtypes = []
        lib.dx_dict_size.restype = ctypes.c_int64
        lib.dx_dict_size.argtypes = [ctypes.c_void_p]
        lib.dx_dict_push.restype = ctypes.c_int32
        lib.dx_dict_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.dx_dict_get.restype = ctypes.c_int64
        lib.dx_dict_get.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64,
        ]
        _lib = lib
        return _lib


def native_available() -> bool:
    """Whether the library builds here — for callers to whom native
    code is optional (test skips, the calibration probe, the wire
    client's checksum). The served ingest path does not ask: it loads
    the library and lets :class:`NativeBuildError` propagate."""
    try:
        load_library()
    except NativeBuildError:
        return False
    return True


def native_crc32c(data: bytes) -> Optional[int]:
    """CRC-32C via the native library (None when unavailable) — shared
    with the wire client so checksum math exists exactly once."""
    if not native_available():
        return None
    return int(load_library().dx_crc32c(data, len(data)))


def packed_shard_bytes() -> int:
    """The bytes from which ``NativeDecoder.decode_packed`` shards what
    it is given (``shard_count`` over 1): a caller that decodes a batch
    in passes keeps a pass at least this large, so that the passes stay
    multi-threaded."""
    return int(load_library().dx_packed_shard_bytes())


def scan_lines(
    buf: bytearray, start: int, stop: int, max_lines: int
) -> Tuple[int, int, int]:
    """Walk the whole lines of ``buf[start:stop]`` until ``max_lines``
    of them that hold more than whitespace are passed: returns (those
    lines' count, the index just past the last line passed, the
    whitespace-only lines passed on the way). An unterminated tail is
    never passed. No object a line, and the interpreter lock is
    released for the walk."""
    if not 0 <= start <= stop <= len(buf):
        raise ValueError(f"scan_lines [{start}, {stop}) of {len(buf)} bytes")
    if start == stop:
        return 0, start, 0
    lib = load_library()
    # the export pins ``buf`` (no resize, no free) for the call
    first = ctypes.c_char.from_buffer(buf, start)
    cut = ctypes.c_int64(0)
    blank = ctypes.c_int64(0)
    lines = lib.dx_scan_lines(
        ctypes.byref(first), stop - start, max_lines,
        ctypes.byref(cut), ctypes.byref(blank),
    )
    return int(lines), start + int(cut.value), int(blank.value)


class NdjsonBuffer:
    """The bytes ``encode_ndjson`` writes a payload into, kept by
    whoever writes payloads one after another (a sink) so that a batch
    neither allocates nor first-touches megabytes. Grown when a payload
    can need more, with a quarter's headroom so that a batch a few rows
    larger than the last does not grow it again. Not locked: its holder
    lets one thread at a time encode into it and finishes with the
    payload before the next encode."""

    def __init__(self):
        self._bytes = np.empty(0, dtype=np.uint8)
        self.grow_count = 0

    def reserve(self, n: int) -> np.ndarray:
        if n > len(self._bytes):
            self._bytes = np.empty(n + n // 4, dtype=np.uint8)
            self.grow_count += 1
        return self._bytes


# dx_encode_ndjson's column kinds (decoder.cpp EncKind), by the dtype
# a rendered column has
_ENC_KIND = {np.dtype(np.int64): 0, np.dtype(np.float64): 1,
             np.dtype(np.bool_): 2}
_ENC_STRING = 3


def _offsets(parts: Sequence[bytes]):
    """``parts`` back to back, and the len(parts) + 1 offsets."""
    off = np.zeros(len(parts) + 1, dtype=np.int64)
    np.cumsum([len(p) for p in parts], out=off[1:])
    return b"".join(parts), off


def encode_ndjson(
    n_rows: int,
    fields: Sequence[Tuple[str, object]],
    out: Optional[NdjsonBuffer] = None,
) -> memoryview:
    """The NDJSON payload of ``n_rows`` rows from their columns: byte
    for byte ``json.dumps(row) + "\\n"`` a row, where a row is the dict of
    ``fields`` in their order.

    ``fields``: (name, column) pairs; a column is an int64, float64 or
    bool array of ``n_rows``, or for strings a pair (the distinct
    values, each a ``str`` or None; an int64 array of ``n_rows``
    indices into them). Names and distinct strings are spelled by
    ``json.dumps`` here, so escaping stays Python's; digits are the
    library's (``float.__repr__``'s for a double, ``NaN`` / ``Infinity``
    as ``json.dumps`` writes them).

    The result is a view of ``out``'s bytes (a buffer of its own when
    none is given), valid until the next encode into ``out``."""
    if n_rows <= 0 or not fields:
        raise ValueError(f"encode_ndjson of {n_rows} rows x {len(fields)}")
    lib = load_library()
    n_cols = len(fields)
    kinds = (ctypes.c_int32 * n_cols)()
    cols = (ctypes.c_void_p * n_cols)()
    str_bytes = (ctypes.c_char_p * n_cols)()
    str_offsets = (ctypes.c_void_p * n_cols)()
    str_counts = (ctypes.c_int64 * n_cols)()
    prefixes = []
    keep = []  # every buffer the call reads, alive until it returns
    for c, (name, col) in enumerate(fields):
        prefixes.append(
            (("{" if c == 0 else ", ") + json.dumps(name) + ": ").encode()
        )
        strings = None
        if isinstance(col, tuple):
            strings, col = col
        if col.shape != (n_rows,):
            raise ValueError(f"column {name!r}: shape {col.shape}")
        if strings is None:
            kind = _ENC_KIND.get(col.dtype)
            if kind is None:
                raise ValueError(f"column {name!r}: dtype {col.dtype}")
            kinds[c] = kind
        else:
            if col.dtype != np.int64 or not (
                0 <= int(col.min()) and int(col.max()) < len(strings)
            ):
                raise ValueError(
                    f"column {name!r}: a string index of {col.dtype} "
                    f"into {len(strings)} strings, or out of their range"
                )
            blob, off = _offsets([json.dumps(s).encode() for s in strings])
            keep.append(off)
            str_bytes[c] = blob
            str_offsets[c] = off.ctypes.data
            str_counts[c] = len(strings)
            kinds[c] = _ENC_STRING
        col = np.ascontiguousarray(col)
        keep.append(col)
        cols[c] = col.ctypes.data
    prefix_blob, prefix_off = _offsets(prefixes)
    prefix_off_p = prefix_off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))

    def encode_into(target: np.ndarray) -> int:
        return lib.dx_encode_ndjson(
            n_rows, n_cols, kinds, cols, prefix_blob, prefix_off_p,
            str_bytes, str_offsets, str_counts,
            target.ctypes.data, len(target),
        )

    buffer = out if out is not None else NdjsonBuffer()
    target = buffer.reserve(0)
    wrote = encode_into(target)
    if wrote < 0:
        # minus the most these rows can take, by the library's own bound
        target = buffer.reserve(-wrote)
        wrote = encode_into(target)
    if wrote <= 0:
        raise RuntimeError(f"dx_encode_ndjson returned {wrote}")
    return memoryview(target)[:wrote]


def _decode_threads(conf_threads: Optional[int] = None) -> int:
    """Decoder shard count: DATAX_DECODER_THREADS env (operator
    override) > the conf'd ``process.ingest.decoderthreads`` > default
    (cap 4 — ingest shares the host with the engine loop and sinks)."""
    env = os.environ.get("DATAX_DECODER_THREADS")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    if conf_threads is not None:
        return max(1, int(conf_threads))
    return max(1, min(4, (os.cpu_count() or 1) - 1))


class PackedBufferPool:
    """Persistent, reused, 64-byte-aligned ingest matrices in the
    packed H2D layout ([n_rows, capacity] int32, row stride ==
    capacity).

    64-byte alignment makes the CPU backend's ``jnp.asarray`` a
    zero-copy view (the same property PR 13 had to defend against for
    ring snapshots) — which is exactly why a matrix may NOT be reused
    while its batch is still in flight: the device step reads the
    buffer directly. The processor releases a slot only once its
    ``PendingBatch`` has landed (or abandoned after the step
    completed), double-buffering the pool against the pipelined
    window. The pool grows on demand (decode-ahead at depth N holds up
    to N+1 matrices) and every reuse is counted for the
    ``Decode_BufferReuse_Count`` metric."""

    def __init__(self, n_rows: int, capacity: int):
        self.n_rows = int(n_rows)
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._free: List[np.ndarray] = []
        self.alloc_count = 0
        self.reuse_count = 0
        self._reuse_drained = 0

    def _new_matrix(self) -> np.ndarray:
        n = self.n_rows * self.capacity
        raw = np.empty(n + 16, dtype=np.int32)
        off = (-raw.ctypes.data % 64) // 4
        m = raw[off: off + n].reshape(self.n_rows, self.capacity)
        assert m.ctypes.data % 64 == 0 and m.flags["C_CONTIGUOUS"]
        return m

    def acquire(self) -> np.ndarray:
        with self._lock:
            if self._free:
                self.reuse_count += 1
                return self._free.pop()
            self.alloc_count += 1
        return self._new_matrix()

    def release(self, matrix: np.ndarray) -> None:
        # an attached BufferSanitizer (debug.buffersanitizer) poisons
        # the slot on release: the pool owns it now, so any sentinel
        # that later surfaces downstream is a use-after-release
        san = getattr(self, "sanitizer", None)
        if san is not None:
            san.poison(matrix)
        with self._lock:
            self._free.append(matrix)

    def take_reuse_count(self) -> int:
        """Reuses since the last take (the Decode_BufferReuse_Count
        delta drained at collect)."""
        with self._lock:
            n = self.reuse_count - self._reuse_drained
            self._reuse_drained = self.reuse_count
            return n


class NativeDecoder:
    """Decode newline-delimited JSON (or Kafka v2 record batches) into
    columnar output typed by the flow's input schema."""

    def __init__(
        self,
        schema: Schema,
        dictionary: StringDictionary,
        threads: Optional[int] = None,
    ):
        self._lib = load_library()
        self.schema = schema
        self.dictionary = dictionary
        # conf'd shard count (datax.job.process.ingest.decoderthreads);
        # None = engine default, env DATAX_DECODER_THREADS always wins
        self.threads = threads
        desc = "".join(
            f"{c.name}\t{_CTYPE_NAME[c.ctype]}\n" for c in schema.columns
        )
        self._d = self._lib.dx_decoder_create(desc.encode("utf-8"))
        self._cols = list(schema.columns)
        self._synced = 0
        self.last_bad_timestamps = 0
        self.last_shards = 1
        self._push_python_entries()

    def close(self):
        if self._d:
            self._lib.dx_decoder_destroy(self._d)
            self._d = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def shard_count(self) -> int:
        return _decode_threads(self.threads)

    # -- dictionary sync --------------------------------------------------
    def _push_python_entries(self):
        """Push Python-side dictionary entries the native map hasn't seen
        (ids are sequential on both sides, so push in id order)."""
        native_n = self._lib.dx_dict_size(self._d)
        py_n = len(self.dictionary)
        for i in range(native_n, py_n):
            s = self.dictionary.decode(i)
            got = self._lib.dx_dict_push(self._d, (s or "").encode("utf-8"))
            if got != i:
                raise RuntimeError(
                    f"dictionary desync: pushed {s!r} expecting id {i}, got {got}"
                )
        self._synced = py_n

    def _pull_native_entries(self):
        """Pull entries the native decode added into the Python dict."""
        native_n = self._lib.dx_dict_size(self._d)
        py_n = len(self.dictionary)
        buf = ctypes.create_string_buffer(4096)
        for i in range(py_n, native_n):
            n = self._lib.dx_dict_get(self._d, i, buf, len(buf))
            if n < 0:
                raise RuntimeError(f"dictionary id {i} missing on native side")
            if n >= len(buf):
                bigger = ctypes.create_string_buffer(int(n) + 1)
                self._lib.dx_dict_get(self._d, i, bigger, len(bigger))
                s = bigger.value.decode("utf-8", "replace")
            else:
                s = buf.value.decode("utf-8", "replace")
            got = self.dictionary.encode(s)
            if got != i:
                raise RuntimeError(
                    f"dictionary desync pulling {s!r}: expected id {i}, got {got}"
                )

    # -- decode -----------------------------------------------------------
    def decode(
        self, data: bytes, max_rows: int
    ) -> Tuple[Dict[str, np.ndarray], np.ndarray, int, int]:
        """Row-layout decode: returns (columns, valid, rows,
        bytes_consumed).

        ``valid`` is the ONLY authoritative row mask: on the sharded
        path malformed lines leave zeroed gap slots at chunk tails, so
        valid rows are NOT a packed prefix and ``arrays[:rows]`` would
        both drop real rows and include gaps. ``rows`` is the
        decoded-row COUNT (== valid.sum()), for metrics."""
        self._push_python_entries()
        arrays: Dict[str, np.ndarray] = {}
        ptrs = (ctypes.c_void_p * len(self._cols))()
        for i, c in enumerate(self._cols):
            a = np.zeros(max_rows, dtype=_NP_DTYPE[c.ctype])
            arrays[c.name] = a
            ptrs[i] = a.ctypes.data_as(ctypes.c_void_p)
        valid = np.zeros(max_rows, dtype=np.uint8)
        consumed = ctypes.c_int64(0)
        n_threads = self.shard_count()
        self.last_shards = n_threads
        rows = self._lib.dx_decode_mt(
            self._d, data, len(data), max_rows, ptrs,
            valid.ctypes.data_as(ctypes.c_void_p), ctypes.byref(consumed),
            n_threads,
        )
        self.last_bad_timestamps = int(self._lib.dx_bad_timestamps(self._d))
        self._pull_native_entries()
        return arrays, valid.astype(bool), int(rows), int(consumed.value)

    def _packed_args(
        self, matrix: np.ndarray, col_rows: Sequence[int], valid_row: int,
        slot: int = 0,
    ):
        if matrix.dtype != np.int32 or not matrix.flags["C_CONTIGUOUS"]:
            raise ValueError("packed decode needs a C-contiguous int32 matrix")
        cr = (ctypes.c_int64 * len(self._cols))(*[int(r) for r in col_rows])
        return (
            ctypes.c_void_p(matrix.ctypes.data + 4 * slot),
            int(matrix.shape[1]), cr, int(valid_row),
        )

    def decode_packed(
        self,
        data,
        matrix: np.ndarray,
        col_rows: Sequence[int],
        valid_row: int,
        base_ms: int,
        max_rows: Optional[int] = None,
        slot: int = 0,
    ) -> Tuple[int, int]:
        """Newline-JSON straight into the packed H2D matrix: column i
        of the schema writes matrix row ``col_rows[i]`` (floats
        bitcast, bools widened, timestamps rebased to int32
        batch-relative ms against ``base_ms``), validity into
        ``matrix[valid_row]`` as int32 0/1. The lines take the row
        slots from ``slot`` on, at most ``max_rows`` of them (default:
        all that are left), and the decoder zeroes exactly those slots
        of its own rows first: reused (dirty) pool matrices are fine,
        and a batch may be decoded in several calls, each from the slot
        the one before stopped at (``max_rows`` that call's line count;
        the last call, left at the default, zeroes the tail). ``data``:
        ``bytes``, or any buffer of bytes that stays as it is for the
        call (a view of the socket source's receive buffer). Returns
        (rows decoded, bytes consumed)."""
        self._push_python_entries()
        left = int(matrix.shape[1]) - slot
        cap = left if max_rows is None else int(max_rows)
        if not 0 <= cap <= left:
            raise ValueError(
                f"packed decode of {cap} rows at slot {slot} of "
                f"{matrix.shape[1]}"
            )
        base, stride, cr, vrow = self._packed_args(
            matrix, col_rows, valid_row, slot
        )
        # the address of any buffer, read-only ones included; ``raw``
        # keeps the bytes alive for the call
        raw = np.frombuffer(data, dtype=np.uint8)
        consumed = ctypes.c_int64(0)
        n_threads = self.shard_count()
        self.last_shards = n_threads
        rows = self._lib.dx_decode_packed(
            self._d, raw.ctypes.data, raw.size, cap, base, stride, cr, vrow,
            int(base_ms), ctypes.byref(consumed), n_threads,
        )
        self.last_bad_timestamps = int(self._lib.dx_bad_timestamps(self._d))
        self._pull_native_entries()
        return int(rows), int(consumed.value)

    def decode_kafka_packed(
        self,
        data: bytes,
        matrix: np.ndarray,
        col_rows: Sequence[int],
        valid_row: int,
        base_ms: int,
        max_rows: Optional[int] = None,
    ) -> Tuple[int, Dict[str, int]]:
        """Kafka v2 record batches straight into the packed H2D matrix
        — CRC-32C verified per batch (corrupt batches skip + count
        instead of mis-parsing), control batches skipped, compressed
        batches rejected with a typed :class:`UnsupportedCodecError`
        naming the codec. Returns (rows decoded, stats) where stats
        carries ``records``/``malformed``/``corrupt_batches``/
        ``control_batches``/``overflow_dropped``."""
        self._push_python_entries()
        base, stride, cr, vrow = self._packed_args(matrix, col_rows, valid_row)
        cap = int(matrix.shape[1]) if max_rows is None else int(max_rows)
        stats = (ctypes.c_int64 * 6)()
        n_threads = self.shard_count()
        self.last_shards = n_threads
        rows = self._lib.dx_decode_kafka_packed(
            self._d, data, len(data), cap, base, stride, cr, vrow,
            int(base_ms), stats, n_threads,
        )
        self.last_bad_timestamps = int(self._lib.dx_bad_timestamps(self._d))
        self._pull_native_entries()
        codec = int(stats[_KSTAT_CODEC])
        if codec >= 0:
            from ..runtime.kafka_wire import UnsupportedCodecError

            raise UnsupportedCodecError(KAFKA_CODEC_NAMES.get(codec, str(codec)))
        return int(rows), {
            "records": int(stats[_KSTAT_RECORDS]),
            "malformed": int(stats[_KSTAT_MALFORMED]),
            "corrupt_batches": int(stats[_KSTAT_CORRUPT]),
            "control_batches": int(stats[_KSTAT_CONTROL]),
            "overflow_dropped": int(stats[_KSTAT_OVERFLOW]),
        }
