"""Encoded execution end-to-end (ops/encoded.py, ISSUE 12): code-space
filter translation, join re-keying through code-translation arrays, the
direct-indexed agg's degrade-to-hash boundary, encoded==decoded result
equivalence across filter/join/agg on NULL-heavy / high-cardinality /
shared-dict / mismatched-dict inputs, fallback accounting
({reason="encoding"}), the EXPLAIN ANALYZE encoding-mode note, and
dictionary-code stability across delta patches."""

import numpy as np
import pytest

import tpch
from tidb_tpu import config, metrics
from tidb_tpu.chunk import Chunk, Column, dict_encode
from tidb_tpu.expression.core import ColumnRef, Constant, Op, func
from tidb_tpu.ops import encoded
from tidb_tpu.ops.hashagg import kernel_for
from tidb_tpu.ops.join import JoinKeyEncoder
from tidb_tpu.session import Session
from tidb_tpu.sqltypes import FieldType, TypeCode, new_string_field
from tidb_tpu.store.storage import new_mock_storage

FT_I = FieldType(tp=TypeCode.LONGLONG)
FT_S = new_string_field()


def _metric(prefix: str) -> float:
    return sum(v for k, v in metrics.snapshot().items()
               if k.startswith(prefix))


def _enc_fallbacks() -> float:
    return sum(v for k, v in metrics.snapshot().items()
               if k.startswith(metrics.DEVICE_FALLBACKS) and
               'reason="encoding"' in k)


def _str_chunk(values, extra_int=None):
    cols = [Column(FT_S,
                   np.array([v if v is not None else "" for v in values],
                            dtype=object),
                   np.array([v is not None for v in values]))]
    if extra_int is not None:
        cols.append(Column(FT_I, np.asarray(extra_int, dtype=np.int64)))
    return Chunk(cols)


class TestTranslateFilter:
    def test_eq_translates_to_code_space(self):
        chunk = _str_chunk(["a", "b", None, "a"])
        f = func(Op.EQ, ColumnRef(0, FT_S, "f"), Constant("a", FT_S))
        t = encoded.translate_filter(f, chunk)
        assert t is not None and t.is_device_safe()
        codes, values = dict_encode(chunk.columns[0])
        d, v = t.eval_xp(np, [(codes, chunk.columns[0].valid)], 4)
        assert list((v & (d != 0)).tolist()) == [True, False, False, True]

    def test_missing_constant_matches_nothing(self):
        chunk = _str_chunk(["a", "b"])
        f = func(Op.EQ, ColumnRef(0, FT_S, "f"), Constant("zz", FT_S))
        t = encoded.translate_filter(f, chunk)
        codes, _ = dict_encode(chunk.columns[0])
        d, v = t.eval_xp(np, [(codes, chunk.columns[0].valid)], 2)
        assert not (v & (d != 0)).any()
        # NE against a missing constant: every valid row passes
        f = func(Op.NE, ColumnRef(0, FT_S, "f"), Constant("zz", FT_S))
        t = encoded.translate_filter(f, chunk)
        d, v = t.eval_xp(np, [(codes, chunk.columns[0].valid)], 2)
        assert (v & (d != 0)).all()

    def test_in_and_logic_mix(self):
        chunk = _str_chunk(["a", "b", "c", None], [1, 2, 3, 4])
        f = func(Op.AND,
                 func(Op.IN, ColumnRef(0, FT_S, "f"),
                      extra=["a", "c", "zz"]),
                 func(Op.GT, ColumnRef(1, FT_I, "i"), Constant(1, FT_I)))
        t = encoded.translate_filter(f, chunk)
        assert t is not None and t.is_device_safe()
        codes, _ = dict_encode(chunk.columns[0])
        cols = [(codes, chunk.columns[0].valid),
                (chunk.columns[1].data, chunk.columns[1].valid)]
        d, v = t.eval_xp(np, cols, 4)
        assert list((v & (d != 0)).tolist()) == [False, False, True,
                                                False]

    def test_is_null_over_codes(self):
        chunk = _str_chunk(["a", None])
        t = encoded.translate_filter(
            func(Op.IS_NULL, ColumnRef(0, FT_S, "f")), chunk)
        codes, _ = dict_encode(chunk.columns[0])
        d, v = t.eval_xp(np, [(codes, chunk.columns[0].valid)], 2)
        assert list((v & (d != 0)).tolist()) == [False, True]

    def test_unsupported_shapes_return_none(self):
        chunk = _str_chunk(["a", "b"])
        ref = ColumnRef(0, FT_S, "f")
        # order comparisons over codes would follow CODE order, not
        # lexical order: must refuse
        assert encoded.translate_filter(
            func(Op.LT, ref, Constant("b", FT_S)), chunk) is None
        assert encoded.translate_filter(
            func(Op.LIKE, ref, Constant("a%", FT_S)), chunk) is None
        # col-vs-col string equality: no constant to pre-encode
        chunk2 = Chunk([chunk.columns[0], chunk.columns[0]])
        assert encoded.translate_filter(
            func(Op.EQ, ref, ColumnRef(1, FT_S, "g")), chunk2) is None

    def test_host_eval_of_code_ref_raises(self):
        chunk = _str_chunk(["a", "b"])
        t = encoded.translate_filter(
            func(Op.EQ, ColumnRef(0, FT_S, "f"), Constant("a", FT_S)),
            chunk)
        ref = t.args[0]
        with pytest.raises(RuntimeError):
            ref.eval(chunk)


class TestCodeTranslation:
    def test_translation_and_null(self):
        src = ["a", "b", "c"]
        dst = ["c", "a"]
        t = encoded.code_translation(src, dst, ci=False)
        codes = np.array([0, 1, 2, -1], dtype=np.int64)
        out = t[codes]
        assert out[0] == 1          # 'a' -> dst code 1
        assert out[1] <= encoded.MISSING_CODE   # 'b' absent
        assert out[2] == 0          # 'c' -> dst code 0
        assert out[3] == -1         # NULL stays NULL

    def test_unmatched_codes_distinct_per_entry(self):
        t = encoded.code_translation(["x", "y"], [], ci=False)
        assert t[0] != t[1] and t[0] <= encoded.MISSING_CODE

    def test_decode_codes_round_trip(self):
        values = ["a", "bb", "ccc"]
        codes = np.array([2, 0, -1, 1], dtype=np.int64)
        out = encoded.decode_codes(values, codes)
        assert list(out) == ["ccc", "a", None, "bb"]


class TestEncoderFastPaths:
    """JoinKeyEncoder's encoded lanes agree with the per-value loop."""

    def _raw(self, vals):
        d = np.array([v if v is not None else "" for v in vals],
                     dtype=object)
        v = np.array([x is not None for x in vals])
        return d, v

    def test_shared_dict_passthrough(self):
        vals = ["a", "b", None, "a", "c"]
        col = _str_chunk(vals).columns[0]
        codes, values = dict_encode(col)
        enc = JoinKeyEncoder(1)
        bk = enc.fit_build([self._raw(vals)],
                           encoded=[(codes, values)], ci=[False])
        pk = enc.transform_probe([self._raw(vals)],
                                 encoded=[(codes, values)])
        # shared dictionary object: codes pass through untranslated
        assert pk[0][0] is codes and bk[0][0] is codes

    def test_mismatched_dicts_rekey_like_raw(self):
        bvals = ["a", "b", "c", None]
        pvals = ["c", "zz", None, "a", "b"]
        bcol = _str_chunk(bvals).columns[0]
        pcol = _str_chunk(pvals).columns[0]
        enc = JoinKeyEncoder(1)
        bk = enc.fit_build([self._raw(bvals)],
                           encoded=[dict_encode(bcol)], ci=[False])
        pk = enc.transform_probe([self._raw(pvals)],
                                 encoded=[dict_encode(pcol)])
        enc2 = JoinKeyEncoder(1)
        bk2 = enc2.fit_build([self._raw(bvals)])
        pk2 = enc2.transform_probe([self._raw(pvals)])
        # identical matching semantics: equal values -> equal codes,
        # absent values negative, NULLs -1
        for j in range(len(pvals)):
            for i in range(len(bvals)):
                match_enc = pk[0][0][j] == bk[0][0][i] and \
                    pk[0][1][j] and bk[0][1][i]
                match_raw = pk2[0][0][j] == bk2[0][0][i] and \
                    pk2[0][1][j] and bk2[0][1][i]
                assert bool(match_enc) == bool(match_raw)
        assert pk[0][0][1] < 0 and pk[0][0][2] == -1

    def test_encoded_build_raw_probe(self):
        """Asymmetric arrival: the lazy mapping from the encoded build
        dictionary serves the raw probe loop."""
        bvals = ["a", "b"]
        bcol = _str_chunk(bvals).columns[0]
        enc = JoinKeyEncoder(1)
        bk = enc.fit_build([self._raw(bvals)],
                           encoded=[dict_encode(bcol)], ci=[False])
        pk = enc.transform_probe([self._raw(["b", "zz", None])])
        assert pk[0][0][0] == bk[0][0][1]
        assert pk[0][0][1] < -1 and pk[0][0][2] == -1


@pytest.fixture(scope="module")
def enc_sess():
    """NULL-heavy, skewed, high-cardinality corpus for the SQL
    property suite; DECIMAL measure so encoded==decoded is exact
    byte-for-byte (scaled-int sums), not approximate."""
    s = Session(new_mock_storage())
    s.execute("CREATE DATABASE enc")
    s.execute("USE enc")
    s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, f VARCHAR(16), "
              "g VARCHAR(16), amt DECIMAL(12,2), i BIGINT)")
    s.execute("CREATE TABLE dim (id BIGINT PRIMARY KEY, k VARCHAR(16), "
              "seg VARCHAR(8))")
    rng = np.random.default_rng(20260804)
    n = 6000
    rows = []
    for i in range(n):
        # ~20% NULLs, skewed head + high-cardinality tail
        f = None if rng.random() < 0.2 else (
            f"hot{i % 3}" if rng.random() < 0.5 else f"v{i % 997}")
        g = f"g{i % 11}"
        rows.append(f"({i}, "
                    f"{'NULL' if f is None else repr(f)}, '{g}', "
                    f"{rng.integers(0, 99999) / 100}, {i % 53})")
    for i in range(0, n, 500):
        s.execute("INSERT INTO t VALUES " + ",".join(rows[i:i + 500]))
    dim = [f"({i}, 'v{i}', 'seg{i % 5}')" for i in range(400)]
    s.execute("INSERT INTO dim VALUES " + ",".join(dim))
    s.execute("SET tidb_tpu_device_min_rows = 1")
    yield s
    s.close()


def _both(s, q):
    """(encoded rows, decoded rows) for one query — byte-for-byte
    comparable (DECIMAL/int outputs only)."""
    s.execute("SET tidb_tpu_encoded_exec = 1")
    enc = s.query(q).rows
    s.execute("SET tidb_tpu_encoded_exec = 0")
    try:
        dec = s.query(q).rows
    finally:
        s.execute("SET tidb_tpu_encoded_exec = 1")
    return enc, dec


class TestEncodedEqualsDecoded:
    @pytest.mark.parametrize("pred", [
        "f = 'hot1'",
        "f != 'hot1'",
        "f IN ('hot0', 'v13', 'absent')",
        "f = 'no-such-value'",
        "f IS NULL",
        "f IS NOT NULL AND i > 25",
        "f = 'hot2' OR f = 'v41'",
    ])
    def test_filtered_agg(self, enc_sess, pred):
        q = (f"SELECT g, COUNT(*), SUM(amt), MIN(i), MAX(i) FROM t "
             f"WHERE {pred} GROUP BY g ORDER BY g")
        enc, dec = _both(enc_sess, q)
        assert enc == dec

    def test_high_cardinality_group(self, enc_sess):
        q = ("SELECT f, COUNT(*), SUM(amt) FROM t WHERE f IS NOT NULL "
             "GROUP BY f ORDER BY f LIMIT 20")
        enc, dec = _both(enc_sess, q)
        assert enc == dec

    def test_string_key_join(self, enc_sess):
        # mismatched dictionaries: t.f's dict vs dim.k's dict
        q = ("SELECT dim.seg, COUNT(*), SUM(t.amt) FROM t "
             "JOIN dim ON t.f = dim.k GROUP BY dim.seg ORDER BY dim.seg")
        enc, dec = _both(enc_sess, q)
        assert enc == dec

    def test_self_join_shared_dict(self, enc_sess):
        # both sides scan the SAME cached column: one dictionary object
        q = ("SELECT COUNT(*) FROM t a JOIN t b ON a.f = b.f "
             "WHERE a.i = 7 AND b.i = 7")
        enc, dec = _both(enc_sess, q)
        assert enc == dec

    def test_left_join_null_semantics(self, enc_sess):
        q = ("SELECT COUNT(*) FROM t LEFT JOIN dim ON t.f = dim.k "
             "WHERE dim.id IS NULL")
        enc, dec = _both(enc_sess, q)
        assert enc == dec


class TestDegradeBoundary:
    def test_force_hash_past_slots(self):
        groups = [ColumnRef(0, FT_S, "f")]
        aggs = []
        k_small = kernel_for(None, groups, aggs, capacity=1024)
        assert not k_small.force_hash       # within the direct bound
        k_big = kernel_for(None, groups, aggs, capacity=16384)
        assert k_big.force_hash             # past tidb_tpu_direct_agg_slots

    def test_degraded_results_match(self, enc_sess):
        s = enc_sess
        prev = config.get_var("tidb_tpu_direct_agg_slots")
        q = ("SELECT f, COUNT(*) FROM t WHERE f IS NOT NULL "
             "GROUP BY f ORDER BY f LIMIT 15")
        want = s.query(q).rows
        try:
            # bound far below the distinct-f domain: every direct-mode
            # kernel degrades to the packed-sort hash table
            s.execute("SET tidb_tpu_direct_agg_slots = 16")
            got = s.query(q).rows
        finally:
            s.execute(f"SET tidb_tpu_direct_agg_slots = {prev}")
        assert got == want


class TestFallbackAccounting:
    def test_unsupported_filter_counts_encoding_reason(self, enc_sess):
        s = enc_sess
        fb0 = _enc_fallbacks()
        rows = s.query("SELECT g, COUNT(*) FROM t WHERE f LIKE 'hot%' "
                       "GROUP BY g ORDER BY g").rows
        assert rows          # sane result through the decoded path
        assert _enc_fallbacks() > fb0

    def test_supported_filter_does_not_count(self, enc_sess):
        s = enc_sess
        fb0 = _enc_fallbacks()
        s.query("SELECT g, COUNT(*) FROM t WHERE f = 'hot0' GROUP BY g")
        assert _enc_fallbacks() == fb0


class TestExplainEncodingMode:
    def test_enc_note_in_pipeline_column(self, enc_sess):
        s = enc_sess
        r = s.query("EXPLAIN ANALYZE SELECT g, COUNT(*) FROM t "
                    "WHERE f = 'hot0' GROUP BY g")
        pc = r.columns.index("pipeline")
        cell = next(row[pc] for row in r.rows
                    if "TableReader" in row[0])
        assert "enc=" in cell and ("direct-agg" in cell or
                                   "encoded" in cell)

    def test_decoded_note_when_translation_fails(self, enc_sess):
        s = enc_sess
        r = s.query("EXPLAIN ANALYZE SELECT g, COUNT(*) FROM t "
                    "WHERE f LIKE 'hot%' GROUP BY g")
        pc = r.columns.index("pipeline")
        cell = next(row[pc] for row in r.rows
                    if "TableReader" in row[0])
        assert "enc=decoded" in cell


class TestDeltaCodeStability:
    """PR 11 pins delta patches extending HBM-block dictionaries in
    place; encoded filters must encode constants against the EXTENDED
    dictionary (code stability: old codes keep their values, new
    values append)."""

    @pytest.fixture()
    def delta_sess(self):
        s = Session(new_mock_storage())
        s.execute("CREATE DATABASE encd")
        s.execute("USE encd")
        s.execute("CREATE TABLE w (id BIGINT PRIMARY KEY, "
                  "f VARCHAR(16), v BIGINT)")
        vals = ",".join(f"({i}, 'k{i % 5}', {i})" for i in range(4096))
        s.execute("INSERT INTO w VALUES " + vals)
        s.execute("SET tidb_tpu_device_min_rows = 1")
        yield s
        s.close()

    def test_codes_stable_across_delta_patch(self, delta_sess):
        s = delta_sess
        q_old = ("SELECT COUNT(*), SUM(v) FROM w WHERE f = 'k1'")
        base = s.query(q_old).rows
        s.query(q_old)          # warm: HBM block + dicts resident
        # the delta introduces a BRAND-NEW dictionary value: the block's
        # dict must extend (not re-encode), and the encoded filter must
        # find the appended code
        s.execute("UPDATE w SET f = 'brandnew' WHERE id = 7")
        fb0 = _enc_fallbacks()
        got_new = s.query(
            "SELECT COUNT(*), SUM(v) FROM w WHERE f = 'brandnew'").rows
        assert got_new == [(1, 7)]
        got_old = s.query(q_old).rows
        assert got_old[0][0] == base[0][0] - (1 if 7 % 5 == 1 else 0)
        assert _enc_fallbacks() == fb0
        # and the unfiltered totals stay exact across the patch
        tot = s.query("SELECT COUNT(*) FROM w").rows
        assert tot == [(4096,)]

    def test_background_merge_keeps_results(self, delta_sess):
        s = delta_sess
        q = "SELECT f, COUNT(*) FROM w WHERE f != 'k3' GROUP BY f " \
            "ORDER BY f"
        s.query(q)
        for i in range(0, 600, 7):
            s.execute(f"UPDATE w SET f = 'moved' WHERE id = {i}")
        s.execute("SET tidb_tpu_device = 0")
        try:
            want = s.query(q).rows
        finally:
            s.execute("SET tidb_tpu_device = 1")
        assert s.query(q).rows == want


# -- the stock TPC-H schema stays encoded ------------------------------------


@pytest.fixture(scope="module")
def tpch_sess():
    s = Session(new_mock_storage())
    s.execute("CREATE DATABASE tpch_enc")
    s.execute("USE tpch_enc")
    tpch.load(s, tpch.TpchData(seed=11))
    s.execute("SET tidb_tpu_device_min_rows = 1")
    yield s
    s.close()


class TestStockTpchStaysEncoded:
    """Q1 (dict group keys + direct-indexed agg) and Q3 (string-filtered
    join chain: encoded join-key lanes + fragment fusion): the feature
    pair on against BOTH off — identical answers, no fallback with
    reason="encoding" (one would mean the vocabulary regressed and warm
    scans silently re-decode), and both bytes-touched counters move."""

    @pytest.mark.parametrize("q", ["Q1", "Q3"])
    def test_no_encoding_fallback_and_bytes_counted(self, tpch_sess, q):
        s, sql = tpch_sess, getattr(tpch, q)
        s.query(sql)                    # compile + cache fill
        fb0 = _enc_fallbacks()
        enc0 = _metric(metrics.BYTES_ENCODED)
        dec0 = _metric(metrics.BYTES_DECODED_EQUIV)
        enc_rows = s.query(sql).rows
        assert _enc_fallbacks() == fb0
        assert _metric(metrics.BYTES_ENCODED) > enc0
        assert _metric(metrics.BYTES_DECODED_EQUIV) > dec0
        s.execute("SET tidb_tpu_encoded_exec = 0")
        s.execute("SET tidb_tpu_fuse_fragments = 0")
        try:
            dec_rows = s.query(sql).rows
        finally:
            s.execute("SET tidb_tpu_encoded_exec = 1")
            s.execute("SET tidb_tpu_fuse_fragments = 1")
        assert enc_rows == dec_rows
