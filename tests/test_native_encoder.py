"""The native NDJSON encoder (``native/decoder.cpp dx_encode_ndjson``,
bound by ``data_accelerator_tpu.native.encode_ndjson``): the sinks'
payload written from a batch's columns.

The oracle in every case is Python's ``json.dumps``, which shares no
code with the encoder: a payload is byte for byte
``json.dumps(row) + "\\n"`` a row.
"""

import json
import threading

import numpy as np
import pytest

from data_accelerator_tpu.native import NdjsonBuffer, encode_ndjson


def per_row_payload(names, columns):
    """``json.dumps`` a row over plain Python values."""
    return "".join(
        json.dumps(dict(zip(names, row))) + "\n" for row in zip(*columns)
    ).encode()


def one_column_payload(name, spellings):
    prefix = "{" + json.dumps(name) + ": "
    return "".join(prefix + s + "}\n" for s in spellings).encode()


def _float_spellings(values: np.ndarray):
    """What ``json.dumps`` writes for each double: one call of its C
    encoder over the list (``float.__repr__``, ``NaN``, ``Infinity``)."""
    return json.dumps(values.tolist())[1:-1].split(", ")


def _decades(rng, n):
    mantissa = rng.uniform(1.0, 10.0, n)
    sign = rng.choice([-1.0, 1.0], n)
    return sign * mantissa * 10.0 ** rng.integers(-30, 30, n)


DOUBLE_FAMILIES = {
    # name -> (values from a generator; how many)
    "normals_across_60_decades": lambda rng: _decades(rng, 600_000),
    "float32_widened": lambda rng: _decades(rng, 250_000).astype(
        np.float32
    ).astype(np.float64),
    "integers_to_1e17": lambda rng: np.concatenate([
        rng.integers(-10**17, 10**17, 60_000).astype(np.float64),
        rng.integers(-10**6, 10**6, 40_000).astype(np.float64),
    ]),
    "short_decimals": lambda rng: np.round(
        rng.uniform(-1000, 1000, 50_000), 2
    ),
    "around_the_layout_edges": lambda rng: np.concatenate([
        rng.uniform(0.5, 2.0, 20_000) * 10.0 ** rng.choice(
            [-6, -5, -4, -3, 14, 15, 16, 17], 20_000
        ),
        np.float64([
            0.0, -0.0, 1e15, 1e16, -1e16, 1e17, 9999999999999998.0,
            999999999999999.9, 1e-4, 1e-5, 0.0001234, 0.00001234,
            1e22, 1e23, 123456789012345678.0, 0.1, 1 / 3, 2 / 3, 100.0,
            5e-324, 2.2250738585072014e-308, -2.2250738585072014e-308,
            1.7976931348623157e308, -1.7976931348623157e308,
            np.nan, -np.nan, np.inf, -np.inf,
        ]),
    ]),
    "powers_of_ten": lambda rng: np.float64(
        [float(f"1e{e}") for e in range(-323, 309)]
        + [float(f"-9.5e{e}") for e in range(-300, 300, 7)]
    ),
    "subnormals_and_bit_patterns": lambda rng: np.concatenate([
        rng.integers(1, 2**52, 20_000, dtype=np.uint64).view(np.float64),
        rng.integers(0, 2**63, 30_000, dtype=np.uint64).view(np.float64),
    ]),
}


def test_double_families_hold_a_million_values():
    rng = np.random.default_rng(0)
    assert sum(len(f(rng)) for f in DOUBLE_FAMILIES.values()) >= 1_000_000


@pytest.mark.parametrize("family", sorted(DOUBLE_FAMILIES))
def test_doubles_are_spelled_as_json_dumps_spells_them(family):
    values = DOUBLE_FAMILIES[family](np.random.default_rng(20261003))
    with np.errstate(all="ignore"):
        values = np.ascontiguousarray(values, dtype=np.float64)
    payload = encode_ndjson(len(values), [("x", values)])
    expected = one_column_payload("x", _float_spellings(values))
    if payload != expected:  # name the first value, not 10 MB of bytes
        got = bytes(payload).split(b"\n")
        for i, want in enumerate(expected.split(b"\n")):
            assert got[i] == want, (i, values[i])
    # the finite spellings parse back to the very doubles
    finite = np.isfinite(values)
    parsed = np.float64(
        [json.loads(line)["x"] for line in bytes(payload).splitlines()]
    )
    assert np.array_equal(parsed[finite], values[finite])


def test_int64_extremes():
    values = np.int64([
        0, 1, -1, 9, 10, -10, 2**31 - 1, -(2**31), 2**53, 10**18,
        2**63 - 1, -(2**63),
    ])
    payload = encode_ndjson(len(values), [("n", values), ("m", values[::-1])])
    assert payload == per_row_payload(
        ["n", "m"], [values.tolist(), values[::-1].tolist()]
    )


def test_strings_names_and_nulls_are_json_dumps_own():
    strings = [
        'say "hi"', "back\\slash", "tab\there\nline\r", "\x00\x01\x1f\x7f",
        "café ☃ \U0001f600", "</script>", "", None, "  ",
        "plain",
    ]
    index = np.int64([9, 0, 1, 2, 3, 4, 5, 6, 7, 8, 0, 9, 7])
    name = 'na"me\\ 100% é\n'
    payload = encode_ndjson(
        len(index), [(name, (strings, index)), ("k", np.arange(13))]
    )
    assert payload == per_row_payload(
        [name, "k"], [[strings[i] for i in index], list(range(13))]
    )
    assert bytes(payload).isascii()  # ensure_ascii, as json.dumps


def test_booleans_and_every_kind_in_one_row():
    n = 1000
    rng = np.random.default_rng(3)
    flags = rng.integers(0, 2, n).astype(np.bool_)
    ints = rng.integers(-10**12, 10**12, n)
    floats = rng.normal(70, 20, n).astype(np.float32).astype(np.float64)
    strings = ["east", "west", None]
    index = rng.integers(0, 3, n)
    payload = encode_ndjson(n, [
        ("b", flags), ("n", ints), ("x", floats), ("s", (strings, index)),
    ])
    assert payload == per_row_payload(
        ["b", "n", "x", "s"],
        [flags.tolist(), ints.tolist(), floats.tolist(),
         [strings[i] for i in index]],
    )
    one = encode_ndjson(1, [("b", flags[:1]), ("x", floats[:1])])
    assert one == per_row_payload(
        ["b", "x"], [flags[:1].tolist(), floats[:1].tolist()]
    )


def _cell_columns(n, seed=5):
    rng = np.random.default_rng(seed)
    return [
        ("deviceId", np.arange(n, dtype=np.int64)),
        ("Cnt", rng.integers(1, 400, n)),
        ("AvgT", rng.uniform(60, 90, n).astype(np.float32).astype(
            np.float64)),
    ]


def _expected(fields):
    return per_row_payload(
        [name for name, _ in fields], [col.tolist() for _, col in fields]
    )


def test_buffer_grows_for_a_large_batch_and_is_reused_for_a_small_one():
    out = NdjsonBuffer()
    small, large = _cell_columns(7), _cell_columns(131_072)
    assert encode_ndjson(7, small, out) == _expected(small)
    assert out.grow_count == 1
    payload = encode_ndjson(131_072, large, out)  # a large one after it
    assert out.grow_count == 2
    assert payload == _expected(large)
    held = out.reserve(0)
    again = encode_ndjson(7, small, out)  # a small one after a large
    assert out.grow_count == 2 and out.reserve(0) is held
    assert again == _expected(small)
    assert again.obj is held  # a view of the kept bytes, not a copy
    encode_ndjson(131_072, large, out)
    assert out.grow_count == 2  # the same batch again: nothing grows
    # without a buffer the payload has one of its own
    assert encode_ndjson(7, small) == _expected(small)


def test_the_widest_value_in_every_column_fills_the_bound_exactly():
    """int64 min is 20 bytes, the widest double 24, ``false`` 5, a
    string its longest spelling: a batch of nothing else takes exactly
    rows x (prefixes + widths + 2), the bound the buffer is sized by,
    so no narrower value can pass it."""
    n = 4096
    strings = ["a", "the longest ☃ of them", None]
    fields = [
        ("n", np.full(n, -(2**63), np.int64)),
        ("x", np.full(n, -2.2250738585072014e-308)),
        ("y", np.full(n, -1.7976931348623157e308)),
        ("b", np.zeros(n, np.bool_)),
        ("s", (strings, np.full(n, 1, np.int64))),
    ]
    row = (
        len('{"n": ') + 20 + len(', "x": ') + 24 + len(', "y": ') + 24
        + len(', "b": ') + 5 + len(', "s": ') + len(json.dumps(strings[1]))
        + len("}\n")
    )
    out = NdjsonBuffer()
    payload = encode_ndjson(n, fields, out)
    assert len(payload) == n * row
    assert len(out.reserve(0)) == n * row + n * row // 4
    assert payload == per_row_payload(
        ["n", "x", "y", "b", "s"],
        [c.tolist() for _, c in fields[:4]] + [[strings[1]] * n],
    )


@pytest.mark.parametrize("fields, n", [
    ([("n", np.int32([1, 2]))], 2),                      # not int64
    ([("x", np.float32([1, 2]))], 2),                    # not float64
    ([("n", np.int64([1, 2, 3]))], 2),                   # another length
    ([("n", np.int64([[1, 2]]))], 2),                    # not a vector
    ([("s", (["a"], np.int64([0, 1])))], 2),             # index past the end
    ([("s", (["a"], np.int64([-1, 0])))], 2),            # negative index
    ([("s", (["a"], np.int32([0, 0])))], 2),             # index not int64
    ([("n", np.int64([]))], 0),                          # no rows
    ([], 3),                                             # no columns
])
def test_what_the_library_cannot_read_is_refused_in_python(fields, n):
    with pytest.raises(ValueError):
        encode_ndjson(n, fields)


def test_a_strided_column_is_read_as_its_values():
    col = np.arange(20, dtype=np.int64)[::2]
    assert not col.flags["C_CONTIGUOUS"]
    assert encode_ndjson(10, [("n", col)]) == per_row_payload(
        ["n"], [col.tolist()]
    )


def test_concurrent_encodes_with_buffers_of_their_own():
    """The call holds no state and releases the interpreter lock: eight
    threads on four cores' worth of work, each with its own buffer."""
    batches = [_cell_columns(20_000 + 1000 * i, seed=i) for i in range(8)]
    expected = [_expected(b) for b in batches]
    wrong = []

    def work(i):
        out = NdjsonBuffer()
        for _ in range(5):
            if encode_ndjson(len(batches[i][0][1]), batches[i], out) \
                    != expected[i]:
                wrong.append(i)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert wrong == []
