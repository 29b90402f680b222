"""Native (C++) codec parity tests.

Ref model: util/codec/codec_test.go + bench — the native decoder must be
bit-identical with the Python reference implementation on every input,
including NULLs, defaults for rows written before ALTER ADD COLUMN,
decimal rescaling, string columns (group edges, non-UTF-8 bytes,
malformed groups), and fallback on JSON / DURATION / wide-decimal layouts.
"""

import decimal
import json
import random
from pathlib import Path

import numpy as np
import pytest

from tidb_tpu import native, tablecodec
from tidb_tpu.schema.model import ColumnInfo, TableInfo
from tidb_tpu.sqltypes import (FieldType, TypeCode, new_date_field,
                               new_decimal_field, new_double_field,
                               new_duration_field, new_int_field,
                               new_string_field)
from tidb_tpu.table import (_kvrows_to_chunk_native, decode_kvrows,
                            kvrows_to_chunk)

pytestmark = pytest.mark.skipif(native.lib() is None,
                                reason="no C++ toolchain")


def _mk_table(cols):
    info = TableInfo(id=77, name="t", columns=[
        ColumnInfo(id=i + 1, name=f"c{i}", offset=i, ft=ft,
                   default=dflt, has_default=dflt is not None or nullable)
        for i, (ft, dflt, nullable) in enumerate(cols)])
    return info


def _encode_rows(info, rows):
    """rows: list of {col_id: datum} -> [(key, value)] record pairs."""
    out = []
    for h, r in enumerate(rows):
        ids = sorted(r)
        out.append((tablecodec.record_key(info.id, h + 1),
                    tablecodec.encode_row(ids, [r[i] for i in ids])))
    return out


# TPC-H's eight tables as the benchmark's generator declares them: the
# schema file gives each column's declared width, from which its type
# follows (8: BIGINT keys, DECIMAL(15,2) otherwise; 4: DATE or INT;
# else CHAR/VARCHAR of that length)
_TPCH = json.loads((Path(__file__).parent.parent / "benchmark" / "generators"
                    / "tpch_dbgen.schema.json").read_text())["tables"]


def _tpch_ft(name, width):
    if width == 8:
        return new_int_field() if name.endswith("key") \
            else new_decimal_field(15, 2)
    if width == 4:
        return new_date_field() if name.endswith("date") \
            else new_int_field()
    return new_string_field(width)


def _python_chunk(info, cols, kvrows, handle_col=None):
    """Force the pure-Python decode path."""
    import tidb_tpu.table as table_mod
    orig = table_mod._kvrows_to_chunk_native
    table_mod._kvrows_to_chunk_native = lambda *a, **k: None
    try:
        return kvrows_to_chunk(info, cols, kvrows, handle_col)
    finally:
        table_mod._kvrows_to_chunk_native = orig


def _assert_chunks_equal(a, b):
    assert a.num_rows == b.num_rows
    assert len(a.columns) == len(b.columns)
    for ca, cb in zip(a.columns, b.columns):
        np.testing.assert_array_equal(np.asarray(ca.valid),
                                      np.asarray(cb.valid))
        va, vb = np.asarray(ca.data), np.asarray(cb.data)
        assert va.dtype == vb.dtype
        if va.dtype == object:
            # every slot, the fill in NULL ones too, and str against bytes
            assert va.tolist() == vb.tolist()
            assert [type(x) for x in va] == [type(x) for x in vb]
        elif va.dtype == np.float64:
            np.testing.assert_allclose(va[ca.valid], vb[cb.valid])
        else:
            np.testing.assert_array_equal(va[ca.valid], vb[cb.valid])


class TestParity:
    def test_mixed_types_with_nulls(self):
        info = _mk_table([(new_int_field(), None, True),
                          (new_double_field(), None, True),
                          (new_decimal_field(12, 2), None, True)])
        rng = random.Random(3)
        rows = []
        for _ in range(500):
            r = {}
            if rng.random() < 0.9:
                r[1] = rng.randint(-2**62, 2**62)
            else:
                r[1] = None
            if rng.random() < 0.9:
                r[2] = rng.uniform(-1e9, 1e9)
            if rng.random() < 0.9:
                r[3] = (2, rng.randint(-10**14, 10**14))
            rows.append(r)
        kvrows = _encode_rows(info, rows)
        got = kvrows_to_chunk(info, info.columns, kvrows, None)
        want = _python_chunk(info, info.columns, kvrows, None)
        _assert_chunks_equal(got, want)

    def test_handle_column_and_subset(self):
        info = _mk_table([(new_int_field(), None, True),
                          (new_double_field(), None, True)])
        rows = [{1: i * 3, 2: i * 0.5} for i in range(100)]
        kvrows = _encode_rows(info, rows)
        cols = [info.columns[1]]      # just the double col
        got = kvrows_to_chunk(info, cols, kvrows, 0)   # handle at pos 0
        want = _python_chunk(info, cols, kvrows, 0)
        _assert_chunks_equal(got, want)
        assert list(got.columns[0].data) == list(range(1, 101))

    def test_missing_column_uses_default(self):
        # rows written before ALTER ADD COLUMN c2 DEFAULT 42
        info = _mk_table([(new_int_field(), None, True),
                          (new_int_field(), 42, False)])
        rows = [{1: i} for i in range(50)]              # c2 absent
        kvrows = _encode_rows(info, rows)
        got = kvrows_to_chunk(info, info.columns, kvrows, None)
        want = _python_chunk(info, info.columns, kvrows, None)
        _assert_chunks_equal(got, want)
        assert all(got.columns[1].data == 42)

    def test_missing_column_null_default(self):
        info = _mk_table([(new_int_field(), None, True),
                          (new_int_field(), None, True)])
        rows = [{1: i} for i in range(10)]
        kvrows = _encode_rows(info, rows)
        got = kvrows_to_chunk(info, info.columns, kvrows, None)
        assert not got.columns[1].valid.any()

    def test_decimal_rescale(self):
        # stored at frac 2, column declared frac 4 (post-MODIFY)
        info = _mk_table([(new_decimal_field(14, 4), None, True)])
        rows = [{1: (2, 12345)}, {1: (4, 98765432)}]
        kvrows = _encode_rows(info, rows)
        got = kvrows_to_chunk(info, info.columns, kvrows, None)
        want = _python_chunk(info, info.columns, kvrows, None)
        _assert_chunks_equal(got, want)
        assert got.columns[0].get(0) == decimal.Decimal("123.45")

    def test_decimal_downscale_rounds_half_away_from_zero(self):
        # stored at frac 4, column declared frac 2: MySQL rounding, both
        # signs, must match the Python path exactly
        info = _mk_table([(new_decimal_field(14, 2), None, True)])
        rows = [{1: (4, 1234567)}, {1: (4, -1234567)},
                {1: (4, 1234550)}, {1: (4, -1234550)},
                {1: (4, 1234449)}, {1: (1, -155)}]
        kvrows = _encode_rows(info, rows)
        got = kvrows_to_chunk(info, info.columns, kvrows, None)
        want = _python_chunk(info, info.columns, kvrows, None)
        _assert_chunks_equal(got, want)
        assert list(got.columns[0].data) == [
            12346, -12346, 12346, -12346, 12344, -1550]

    def test_huge_frac_shift_falls_back(self):
        # a >18-digit downscale would overflow pow10_i64: native declines,
        # python divides exactly
        info = _mk_table([(new_decimal_field(30, 0), None, True)])
        rows = [{1: (20, 12345)}, {1: (0, 42)}]
        kvrows = _encode_rows(info, rows)
        got = kvrows_to_chunk(info, info.columns, kvrows, None)
        want = _python_chunk(info, info.columns, kvrows, None)
        _assert_chunks_equal(got, want)
        assert list(got.columns[0].data) == [0, 42]

    def test_string_column_decodes_natively(self):
        info = _mk_table([(new_int_field(), None, True),
                          (new_string_field(), None, True)])
        rows = [{1: i, 2: f"s{i}"} for i in range(20)]
        kvrows = _encode_rows(info, rows)
        got = _kvrows_to_chunk_native(info.columns, kvrows, None)
        assert got is not None
        _assert_chunks_equal(got, _python_chunk(info, info.columns, kvrows))
        ch = kvrows_to_chunk(info, info.columns, kvrows, None)
        assert ch.columns[1].get(5) == "s5"

    @pytest.mark.parametrize("ft,val", [
        (FieldType(TypeCode.JSON), '{"a":1}'),
        (new_duration_field(), 3_600_000_000),
    ], ids=["json", "duration"])
    def test_json_and_duration_columns_fall_back(self, ft, val):
        info = _mk_table([(new_int_field(), None, True), (ft, None, True)])
        kvrows = _encode_rows(info, [{1: i, 2: val} for i in range(20)])
        assert _kvrows_to_chunk_native(info.columns, kvrows, None) is None
        ch = kvrows_to_chunk(info, info.columns, kvrows, None)
        assert ch.columns[1].data[5] == val
        # the fixed-width column beside it alone is the walker's again
        assert _kvrows_to_chunk_native(info.columns[:1], kvrows,
                                       None) is not None

    def test_wide_decimal_table_keeps_the_python_path(self):
        info = _mk_table([(new_string_field(), None, True),
                          (new_decimal_field(30, 2), None, True)])
        kvrows = _encode_rows(info, [{1: f"s{i}", 2: (2, 10 ** 25 + i)}
                                     for i in range(10)])
        ch, native_built = decode_kvrows(info, info.columns[:1], kvrows)
        assert not native_built
        _assert_chunks_equal(ch, _python_chunk(info, info.columns[:1],
                                               kvrows))

    @pytest.mark.parametrize("length", [0, 7, 8, 9, 16, 17, 200])
    def test_string_lengths_at_group_edges(self, length):
        info = _mk_table([(new_string_field(), None, True)])
        text = ("the quick brown fox jumps over the lazy dog " * 5)[:length]
        rows = [{1: text}, {1: text[::-1]}, {1: ""}, {1: text}]
        kvrows = _encode_rows(info, rows)
        got = _kvrows_to_chunk_native(info.columns, kvrows, None)
        assert got is not None
        _assert_chunks_equal(got, _python_chunk(info, info.columns, kvrows))
        assert got.columns[0].data.tolist() == [r[1] for r in rows]

    @pytest.mark.parametrize("default,nullable", [
        (None, True), ("dflt", False), (b"\xff\xfe", False), ("", False),
        ("h\u00e9", False)],
        ids=["null", "str", "non-utf8", "empty", "multibyte"])
    def test_string_nulls_and_missing_column_defaults(self, default,
                                                      nullable):
        # c1 written before ALTER ADD COLUMN in two rows of three; an
        # explicit NULL in every fifth
        info = _mk_table([(new_int_field(), None, True),
                          (new_string_field(), default, nullable)])
        rows = []
        for i in range(60):
            r = {1: i}
            if i % 3 == 0:
                r[2] = None if i % 5 == 0 else f"v{i}"
            rows.append(r)
        kvrows = _encode_rows(info, rows)
        got = _kvrows_to_chunk_native(info.columns, kvrows, None)
        assert got is not None
        _assert_chunks_equal(got, _python_chunk(info, info.columns, kvrows))
        col = got.columns[1]
        assert col.valid[3] and col.data[3] == "v3"
        assert not col.valid[15] and col.data[15] == ""
        assert bool(col.valid[1]) == (default is not None)
        assert col.data[1] == ("" if default is None else default)

    def test_non_utf8_bytes_stay_bytes_and_multibyte_utf8_is_str(self):
        info = _mk_table([(FieldType(TypeCode.BLOB), None, True),
                          (new_string_field(), None, True)])
        rows = [{1: b"\xff\x00\xfe binary", 2: "plain"},
                {1: b"ascii bytes", 2: "gr\u00fc\u00dfe \u4e16\u754c \U0001f600"},
                {1: "\u00e9".encode("utf8")[:1], 2: "x" * 8 + "\u00e9"},
                {1: b"", 2: None}]
        kvrows = _encode_rows(info, rows)
        got = _kvrows_to_chunk_native(info.columns, kvrows, None)
        assert got is not None
        _assert_chunks_equal(got, _python_chunk(info, info.columns, kvrows))
        assert got.columns[0].data.tolist() == [
            b"\xff\x00\xfe binary", "ascii bytes", b"\xc3", ""]
        assert got.columns[1].data[1] == "gr\u00fc\u00dfe \u4e16\u754c \U0001f600"

    @pytest.mark.parametrize("handle_col", [None, 0, 2])
    def test_string_between_a_skipped_one_and_fixed_width(self, handle_col):
        info = _mk_table([(new_string_field(), None, True),
                          (new_string_field(), None, True),
                          (new_int_field(), None, True),
                          (new_decimal_field(15, 2), None, True),
                          (new_string_field(), None, True)])
        rows = [{1: "skip me " * (i % 4), 2: f"keep{i}" * (i % 3), 3: i,
                 4: (2, i * 101), 5: None if i % 4 == 0 else "t" * i}
                for i in range(40)]
        kvrows = _encode_rows(info, rows)
        for cols in (info.columns[1:], [info.columns[4], info.columns[2],
                                        info.columns[1]]):
            got = _kvrows_to_chunk_native(cols, kvrows, handle_col)
            assert got is not None
            _assert_chunks_equal(
                got, _python_chunk(info, cols, kvrows, handle_col))
        if handle_col is not None:
            assert got.columns[handle_col].data.tolist() == \
                list(range(1, 41))

    @pytest.mark.parametrize("fault", ["marker", "padding", "truncated"])
    def test_malformed_group_is_pythons_error(self, fault):
        info = _mk_table([(new_int_field(), None, True),
                          (new_string_field(), None, True)])
        kvrows = _encode_rows(info, [{1: i, 2: "abc"} for i in range(4)])
        k, v = kvrows[2]
        # the value ends [BYTES_FLAG] "abc" + 5 pad bytes + marker 0xFA
        assert v[-9:] == b"abc\x00\x00\x00\x00\x00\xfa"
        bad = {"marker": v[:-1] + b"\xf0",
               "padding": v[:-2] + b"\x01\xfa",
               "truncated": v[:-3]}[fault]
        kvrows[2] = (k, bad)
        assert _kvrows_to_chunk_native(info.columns, kvrows, None) is None
        with pytest.raises(ValueError):
            kvrows_to_chunk(info, info.columns, kvrows, None)
        # also when the string is only walked over
        if fault != "truncated":
            assert _kvrows_to_chunk_native(info.columns[:1], kvrows,
                                           None) is None

    def test_datum_of_the_other_kind_falls_back(self):
        info = _mk_table([(new_int_field(), None, True),
                          (new_string_field(), None, True)])
        # a number stored under the string column: python keeps the int
        kvrows = _encode_rows(info, [{1: 1, 2: "a"}, {1: 2, 2: 7}])
        assert _kvrows_to_chunk_native(info.columns, kvrows, None) is None
        ch = kvrows_to_chunk(info, info.columns, kvrows, None)
        assert ch.columns[1].data.tolist() == ["a", 7]
        # a string stored under the int column
        kvrows = _encode_rows(info, [{1: "x", 2: "a"}])
        assert _kvrows_to_chunk_native(info.columns, kvrows, None) is None

    def test_extra_stored_columns_skipped(self):
        # rows contain a dropped column's leftovers (incl. a string)
        info = _mk_table([(new_int_field(), None, True)])
        rows = [{1: i, 9: f"dead{i}", 10: 3.25} for i in range(30)]
        kvrows = _encode_rows(info, rows)
        got = kvrows_to_chunk(info, info.columns, kvrows, None)
        want = _python_chunk(info, info.columns, kvrows, None)
        _assert_chunks_equal(got, want)

    def test_fuzz_roundtrip(self):
        rng = random.Random(11)
        for _trial in range(20):
            ncols = rng.randint(1, 5)
            cols = []
            for _ in range(ncols):
                cols.append(rng.choice([
                    (new_int_field(), None, True),
                    (new_double_field(), None, True),
                    (new_decimal_field(12, rng.randint(0, 4)), None, True),
                ]))
            info = _mk_table(cols)
            rows = []
            for _ in range(rng.randint(0, 60)):
                r = {}
                for ci in info.columns:
                    if rng.random() < 0.15:
                        continue            # absent
                    if rng.random() < 0.1:
                        r[ci.id] = None     # explicit NULL
                    elif ci.ft.tp == TypeCode.NEWDECIMAL:
                        r[ci.id] = (ci.ft.frac,
                                    rng.randint(-10**12, 10**12))
                    elif ci.ft.tp == TypeCode.DOUBLE:
                        r[ci.id] = rng.uniform(-1e12, 1e12)
                    else:
                        r[ci.id] = rng.randint(-2**60, 2**60)
                rows.append(r)
            kvrows = _encode_rows(info, rows)
            got = kvrows_to_chunk(info, info.columns, kvrows, None)
            want = _python_chunk(info, info.columns, kvrows, None)
            _assert_chunks_equal(got, want)

    @pytest.mark.parametrize("table", sorted(_TPCH))
    def test_fuzz_tpch_layouts(self, table):
        info = _mk_table([(_tpch_ft(name, width), None, True)
                          for name, width in _TPCH[table].items()])
        rng = random.Random(sum(map(ord, table)))
        alphabet = "abcdefghij klmnop,.-ABC" + "\u00e9\u4e16"
        for trial in range(6):
            rows = []
            for _ in range(rng.randint(0, 80)):
                r = {}
                for ci in info.columns:
                    if rng.random() < 0.05:
                        continue            # absent
                    if rng.random() < 0.05:
                        r[ci.id] = None     # explicit NULL
                    elif ci.ft.tp == TypeCode.NEWDECIMAL:
                        r[ci.id] = (2, rng.randint(-10**12, 10**12))
                    elif ci.ft.tp == TypeCode.VARCHAR:
                        # ASCII-only batches on even trials: the bulk path
                        chars = alphabet[:-2] if trial % 2 == 0 else alphabet
                        r[ci.id] = "".join(
                            rng.choice(chars)
                            for _ in range(rng.randint(0, ci.ft.flen)))
                    else:
                        r[ci.id] = rng.randint(0, 2**40)
                rows.append(r)
            kvrows = _encode_rows(info, rows)
            cols = rng.sample(info.columns,
                              rng.randint(1, len(info.columns)))
            handle_col = rng.choice([None, 0, len(cols)])
            got = _kvrows_to_chunk_native(cols, kvrows, handle_col)
            assert got is not None
            _assert_chunks_equal(
                got, _python_chunk(info, cols, kvrows, handle_col))

    @pytest.mark.parametrize("seed", range(8))
    def test_pruned_subsets_of_full_width_rows(self, seed, monkeypatch):
        """What column pruning asks of the decoder: any subset of
        lineitem's 16 columns, in any order, strings in or out of it,
        with an explicit NULL, and rows written before two ADD COLUMNs
        (an INT with a default, a VARCHAR with one): the C++ chunk equals
        the Python decoder's, and an object lane is built for the string
        columns asked for and for no other."""
        import tidb_tpu.table as table_mod
        layout = [(_tpch_ft(name, width), None, True)
                  for name, width in _TPCH["lineitem"].items()]
        layout += [(new_int_field(), 42, False),
                   (new_string_field(8), "dflt", False)]
        info = _mk_table(layout)
        added = {c.id for c in info.columns[-2:]}
        rng = random.Random(1000 + seed)
        rows = []
        for i in range(60):
            r = {}
            for ci in info.columns:
                if ci.id in added and i < 30:
                    continue            # written before ADD COLUMN
                if rng.random() < 0.05 and ci.id not in added:
                    r[ci.id] = None
                elif ci.ft.tp == TypeCode.NEWDECIMAL:
                    r[ci.id] = (2, rng.randint(-10**12, 10**12))
                elif ci.ft.tp == TypeCode.VARCHAR:
                    r[ci.id] = "".join(
                        rng.choice("abc de,.\u00e9" if seed % 2 else "abc de,.")
                        for _ in range(rng.randint(0, ci.ft.flen)))
                else:
                    r[ci.id] = rng.randint(0, 2**40)
            rows.append(r)
        rows[7][info.columns[0].id] = None          # a NULL for certain
        kvrows = _encode_rows(info, rows)
        strings = {c.id for c in info.columns
                   if c.ft.tp == TypeCode.VARCHAR}
        lanes = []
        orig = table_mod._strings_from_spans
        monkeypatch.setattr(
            table_mod, "_strings_from_spans",
            lambda *a, **k: lanes.append(1) or orig(*a, **k))
        for _trial in range(6):
            cols = rng.sample(info.columns,
                              rng.randint(1, len(info.columns)))
            if _trial == 0:             # no string asked for at all
                cols = [c for c in cols if c.id not in strings] \
                    or [info.columns[0]]
            handle_col = rng.choice([None, 0, len(cols)])
            del lanes[:]
            got = _kvrows_to_chunk_native(cols, kvrows, handle_col)
            assert got is not None
            assert len(lanes) == sum(c.id in strings for c in cols)
            want = _python_chunk(info, cols, kvrows, handle_col)
            _assert_chunks_equal(got, want)
            for c, lane in zip(cols, [col for j, col in
                                      enumerate(got.columns)
                                      if j != handle_col]):
                if c.id in added:
                    assert lane.data[:30].tolist() == [c.default] * 30


class TestBatchPrimitives:
    def test_encode_decode_int_batch(self):
        import ctypes
        cdll = native.lib()
        cdll.encode_int_batch.argtypes = [
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.c_char_p]
        cdll.decode_int_batch.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64)]
        vals = np.array([0, 1, -1, 2**62, -2**62, 123456789],
                        dtype=np.int64)
        out = ctypes.create_string_buffer(len(vals) * 8)
        cdll.encode_int_batch(
            vals.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(vals), out)
        from tidb_tpu import codec
        for i, v in enumerate(vals):
            assert out.raw[i * 8:(i + 1) * 8] == codec.encode_int(int(v))
        back = np.zeros(len(vals), dtype=np.int64)
        cdll.decode_int_batch(
            out.raw, len(vals),
            back.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        np.testing.assert_array_equal(back, vals)


class TestPerf:
    def test_native_not_slower(self):
        """Decode 20k rows both ways; native must at least keep up (it is
        typically ~10-30x faster; generous 1.0x bound avoids CI flakes)."""
        import time
        info = _mk_table([(new_int_field(), None, True),
                          (new_double_field(), None, True),
                          (new_int_field(), None, True)])
        rows = [{1: i, 2: i * 0.5, 3: i * 7} for i in range(20000)]
        kvrows = _encode_rows(info, rows)
        t0 = time.perf_counter()
        got = kvrows_to_chunk(info, info.columns, kvrows, None)
        t_native = time.perf_counter() - t0
        t0 = time.perf_counter()
        want = _python_chunk(info, info.columns, kvrows, None)
        t_python = time.perf_counter() - t0
        _assert_chunks_equal(got, want)
        assert t_native <= t_python, (t_native, t_python)

    def test_native_not_slower_on_full_width_rows(self):
        """lineitem's sixteen columns, five of them strings: the rows the
        streamed joins decode (typically ~20-30x faster natively)."""
        import time
        info = _mk_table([(_tpch_ft(name, width), None, True)
                          for name, width in _TPCH["lineitem"].items()])
        rows = [{ci.id: ("c" * (1 + (i + ci.id) % ci.ft.flen)
                         if ci.ft.tp == TypeCode.VARCHAR
                         else (2, i * 7) if ci.ft.tp == TypeCode.NEWDECIMAL
                         else i)
                 for ci in info.columns} for i in range(5000)]
        kvrows = _encode_rows(info, rows)
        t0 = time.perf_counter()
        got = _kvrows_to_chunk_native(info.columns, kvrows, None)
        t_native = time.perf_counter() - t0
        t0 = time.perf_counter()
        want = _python_chunk(info, info.columns, kvrows, None)
        t_python = time.perf_counter() - t0
        assert got is not None
        _assert_chunks_equal(got, want)
        assert t_native <= t_python, (t_native, t_python)
