"""Device aggregation kernel tests: agree with a naive numpy/python oracle.

Ref model: executor/aggregate_test.go + mocktikv/aggregate.go behavior.
Runs on the CPU backend (conftest pins platforms) but the same XLA programs
compile for TPU.
"""

import decimal
import random
from collections import defaultdict

import numpy as np
import pytest

from tidb_tpu import sqltypes as st
from tidb_tpu.chunk import Chunk
from tidb_tpu.expression import AggDesc, AggFunc, Op, col, const, func
from tidb_tpu.ops.hashagg import (CapacityError, HashAggKernel,
                                  HashAggregator, ScalarAggKernel)

INT = st.new_int_field()
DBL = st.new_double_field()
DEC2 = st.new_decimal_field(frac=2)
STR = st.new_string_field()


def oracle_agg(rows, key_fn, val_fn, agg):
    groups = defaultdict(list)
    for r in rows:
        k = key_fn(r)
        if k is not None:
            groups[k].append(val_fn(r))
    return groups


def test_sum_count_by_int_key():
    rng = random.Random(1)
    rows = [(rng.randrange(5), rng.randrange(100)) for _ in range(3000)]
    ch = Chunk.from_rows([INT, INT], rows)
    k = HashAggKernel(None, [col(0, INT)],
                      [AggDesc(AggFunc.SUM, col(1, INT)),
                       AggDesc(AggFunc.COUNT, None)])
    agg = HashAggregator(k.aggs)
    agg.update(k(ch))
    got = {key[0]: tuple(v) for key, v in agg.results()}
    exp = defaultdict(lambda: [0, 0])
    for a, b in rows:
        exp[a][0] += b
        exp[a][1] += 1
    assert got == {k2: (v[0], v[1]) for k2, v in exp.items()}


def test_filter_and_group_with_nulls():
    rows = [(1, 10), (1, None), (2, 5), (None, 7), (2, 3), (1, 2)]
    ch = Chunk.from_rows([INT, INT], rows)
    # WHERE v >= 3
    k = HashAggKernel(col(1, INT).ge(3), [col(0, INT)],
                      [AggDesc(AggFunc.SUM, col(1, INT)),
                       AggDesc(AggFunc.COUNT, None),
                       AggDesc(AggFunc.MIN, col(1, INT)),
                       AggDesc(AggFunc.MAX, col(1, INT))])
    agg = HashAggregator(k.aggs)
    agg.update(k(ch))
    res = {key[0]: v for key, v in agg.results()}
    assert res[1] == [10, 1, 10, 10]
    assert res[2] == [8, 2, 3, 5]
    assert res[None] == [7, 1, 7, 7]  # NULL is its own group
    # row (1, None) dropped by filter; row (1,2) filtered out


def test_string_group_key():
    rows = [("aa", 1), ("bb", 2), ("aa", 3), (None, 4), ("cc", 5), ("bb", 6)]
    ch = Chunk.from_rows([STR, INT], rows)
    k = HashAggKernel(None, [col(0, STR)],
                      [AggDesc(AggFunc.SUM, col(1, INT))])
    agg = HashAggregator(k.aggs)
    agg.update(k(ch))
    res = {key[0]: v[0] for key, v in agg.results()}
    assert res == {"aa": 4, "bb": 8, "cc": 5, None: 4}


def test_multi_chunk_merge():
    k = HashAggKernel(None, [col(0, INT)],
                      [AggDesc(AggFunc.SUM, col(1, INT)),
                       AggDesc(AggFunc.AVG, col(1, DBL)),
                       AggDesc(AggFunc.MIN, col(1, INT))])
    agg = HashAggregator(k.aggs)
    all_rows = []
    rng = random.Random(2)
    for _ in range(4):
        rows = [(rng.randrange(3), rng.randrange(1000)) for _ in range(500)]
        all_rows += rows
        agg.update(k(Chunk.from_rows([INT, INT], rows)))
    res = {key[0]: v for key, v in agg.results()}
    for g in range(3):
        vals = [b for a, b in all_rows if a == g]
        assert res[g][0] == sum(vals)
        assert abs(res[g][1] - sum(vals) / len(vals)) < 1e-9
        assert res[g][2] == min(vals)


def test_decimal_sum_avg():
    rows = [(1, decimal.Decimal("1.50")), (1, decimal.Decimal("2.25")),
            (2, decimal.Decimal("-0.75")), (1, None)]
    ch = Chunk.from_rows([INT, DEC2], rows)
    aggs = [AggDesc(AggFunc.SUM, col(1, DEC2)),
            AggDesc(AggFunc.AVG, col(1, DEC2))]
    k = HashAggKernel(None, [col(0, INT)], aggs)
    agg = HashAggregator(aggs)
    agg.update(k(ch))
    res = {key[0]: v for key, v in agg.results()}
    assert res[1][0] == 375        # 3.75 @ frac2
    # avg result frac = 2+4 = 6: 1.875 -> 1875000
    assert aggs[1].result_ft.frac == 6
    assert res[1][1] == 1_875_000
    assert res[2][0] == -75


def test_avg_sum_real():
    rows = [(1, 1.5), (1, 2.5), (2, None)]
    ch = Chunk.from_rows([INT, DBL], rows)
    aggs = [AggDesc(AggFunc.SUM, col(1, DBL)),
            AggDesc(AggFunc.AVG, col(1, DBL)),
            AggDesc(AggFunc.COUNT, col(1, DBL))]
    k = HashAggKernel(None, [col(0, INT)], aggs)
    agg = HashAggregator(aggs)
    agg.update(k(ch))
    res = {key[0]: v for key, v in agg.results()}
    assert res[1] == [4.0, 2.0, 2]
    assert res[2] == [None, None, 0]  # all-null group


def test_expression_group_key():
    # GROUP BY a % 3
    rows = [(i, i * 10) for i in range(100)]
    ch = Chunk.from_rows([INT, INT], rows)
    gexpr = func(Op.MOD, col(0, INT), const(3))
    k = HashAggKernel(None, [gexpr], [AggDesc(AggFunc.COUNT, None)])
    agg = HashAggregator(k.aggs)
    agg.update(k(ch))
    res = {key[0]: v[0] for key, v in agg.results()}
    assert res == {0: 34, 1: 33, 2: 33}


def test_scalar_agg():
    rows = [(i, float(i)) for i in range(1000)]
    ch = Chunk.from_rows([INT, DBL], rows)
    aggs = [AggDesc(AggFunc.SUM, col(0, INT)),
            AggDesc(AggFunc.COUNT, None),
            AggDesc(AggFunc.MAX, col(1, DBL))]
    k = ScalarAggKernel(col(0, INT).lt(500), aggs)
    agg = HashAggregator(aggs)
    agg.update(k(ch))
    [(key, vals)] = agg.results()
    assert key == ()
    assert vals == [sum(range(500)), 500, 499.0]


def test_first_row():
    rows = [(1, "x"), (2, "y"), (1, "z")]
    ch = Chunk.from_rows([INT, STR], rows)
    aggs = [AggDesc(AggFunc.FIRST_ROW, col(1, STR))]
    k = HashAggKernel(None, [col(0, INT)], aggs)
    agg = HashAggregator(aggs)
    agg.update(k(ch))
    res = {key[0]: v[0] for key, v in agg.results()}
    assert res == {1: "x", 2: "y"}


def test_capacity_overflow_detected():
    rows = [(i,) for i in range(200)]
    ch = Chunk.from_rows([INT], rows)
    k = HashAggKernel(None, [col(0, INT)],
                      [AggDesc(AggFunc.COUNT, None)], capacity=64)
    with pytest.raises(CapacityError):
        k(ch)


def test_device_safety_validation():
    with pytest.raises(ValueError):
        HashAggKernel(func(Op.LIKE, col(0, STR), extra="%x%"), [col(1, INT)],
                      [AggDesc(AggFunc.COUNT, None)])
    with pytest.raises(ValueError):
        HashAggKernel(None, [func(Op.UPPER, col(0, STR))],
                      [AggDesc(AggFunc.COUNT, None)])
    with pytest.raises(ValueError):
        HashAggKernel(None, [col(1, INT)],
                      [AggDesc(AggFunc.MIN, col(0, STR))])


def test_empty_chunk_and_no_match_filter():
    ch = Chunk.from_rows([INT, INT], [(1, 2)])
    k = HashAggKernel(col(1, INT).gt(100), [col(0, INT)],
                      [AggDesc(AggFunc.SUM, col(1, INT))])
    agg = HashAggregator(k.aggs)
    agg.update(k(ch))
    assert agg.results() == []


def test_hashagg_exec_replans_capacity_overflow():
    """>capacity distinct groups: HashAggExec re-plans the device kernel
    with a larger table instead of losing the device path (the re-plan
    promised by the kernel docstring)."""
    from tidb_tpu.executor import HashAggExec
    from tidb_tpu.plan.physical import PhysHashAgg
    from tidb_tpu.plan.resolver import PlanSchema, SchemaCol

    n, ngroups = 6000, 5000
    rows = [(i % ngroups, i) for i in range(n)]
    ch = Chunk.from_rows([INT, INT], rows)

    class _Child:
        schema = None

        def chunks(self, ctx):
            yield ch

    plan = PhysHashAgg(
        schema=PlanSchema([SchemaCol("g", "", INT),
                           SchemaCol("s", "", st.new_int_field())]),
        children=[None],
        group_exprs=[col(0, INT)],
        aggs=[AggDesc(AggFunc.SUM, col(1, INT))])
    exe = HashAggExec.__new__(HashAggExec)
    exe.plan, exe.schema, exe.child, exe._kernel = plan, plan.schema, \
        _Child(), None
    out = list(exe.chunks(None))[0]
    assert out.num_rows == ngroups
    # the kernel was re-planned (not abandoned) with a larger capacity
    assert exe._kernel is not None and exe._kernel.capacity >= ngroups


def test_cond_direct_wide_span_takes_hash_branch():
    """BIGINT keys spanning more than 2^63: the int64 code math wraps,
    so the smallness decision must come from raw min/max in float64 and
    route to the hash branch (device path preserved, no collisions)."""
    import numpy as np
    from tidb_tpu.chunk import Chunk, Column
    from tidb_tpu.expression import AggDesc, AggFunc
    from tidb_tpu.expression.core import col
    from tidb_tpu.ops.hashagg import HashAggKernel
    from tidb_tpu.sqltypes import new_int_field
    n = 64
    keys = np.where(np.arange(n) % 2 == 0, -(2 ** 62), 2 ** 62)
    ch = Chunk([Column(new_int_field(), keys.astype(np.int64),
                       np.ones(n, bool)),
                Column(new_int_field(), np.ones(n, dtype=np.int64),
                       np.ones(n, bool))])
    k = HashAggKernel(None, [col(0, new_int_field(), "k")],
                      [AggDesc(AggFunc.SUM, col(1, new_int_field()))],
                      capacity=64)
    gr = k(ch)          # must not raise CollisionError
    assert sorted(int(c) for c in gr.counts) == [32, 32]


# -- dense against scatter against the host executor (PR 25) -----------------

import jax  # noqa: E402

from tidb_tpu import metrics  # noqa: E402
from tidb_tpu.ops import hashagg  # noqa: E402
from tidb_tpu.ops.hostagg import host_hash_agg  # noqa: E402

D = hashagg._DENSE_SLOTS
BIG = 1 << 55                      # on every 20th row: 205 a bucket fit int64
_ROWS = 4096                       # one bucket for every counted case
_VAL_FT = [INT, DEC2, INT, INT]   # SUM, AVG, MIN/MAX/COUNT, FIRST_ROW


def _dense_aggs():
    return [AggDesc(AggFunc.SUM, col(1, INT)),
            AggDesc(AggFunc.AVG, col(2, DEC2)),
            AggDesc(AggFunc.MIN, col(3, INT)),
            AggDesc(AggFunc.MAX, col(3, INT)),
            AggDesc(AggFunc.COUNT, col(3, INT)),
            AggDesc(AggFunc.COUNT, None),
            AggDesc(AggFunc.FIRST_ROW, col(4, INT))]


def _dense_parts(mode):
    """(key type, group exprs, force_hash) of one group-table mode."""
    if mode == "direct":           # dictionary-coded string keys
        return STR, [col(0, STR)], False
    if mode == "cond":             # bare int keys: direct at run time
        return INT, [col(0, INT)], False
    return INT, [col(0, INT)], True   # the packed sort over the hash


def _dense_chunk(mode, groups, rows, null_keys):
    """`rows` rows over `groups` distinct keys (every key used), group
    g's MIN/MAX argument all NULL when g % 5 == 0 (an empty slot for
    those lanes), sums past 2^53 with odd low bits; with null_keys, a
    NULL group too."""
    rng = np.random.default_rng(groups * 7 + rows)
    nnull = rows // 11 if null_keys else 0
    g = np.concatenate([np.arange(groups),
                        rng.integers(0, groups, rows - groups - nnull),
                        np.full(nnull, -1)]) if rows else np.zeros(0, int)
    rng.shuffle(g)
    out = []
    for i, gi in enumerate(g.tolist()):
        key = None if gi < 0 else gi if mode != "direct" else f"k{gi:05d}"
        out.append((key, (BIG if i % 20 == 0 else 0) + int(rng.integers(1000)),
                    decimal.Decimal(int(rng.integers(-10**6, 10**6))) / 100,
                    None if gi % 5 == 0 else int(rng.integers(-50, 50)),
                    i))
    return Chunk.from_rows([_dense_parts(mode)[0]] + _VAL_FT, out)


def _dispatches():
    snap = metrics.snapshot()
    return {p: snap.get('tidb_tpu_agg_dispatch_total{path="%s"}' % p, 0)
            for p in ("dense", "scatter")}


def _run_both(monkeypatch, mode, ch, filt=None):
    """One block through the program as it is and through the program
    with the dense branch shut (a table of 0 slots never fits): ->
    the two raw result pytrees, and the two finalized GroupResults."""
    _kt, groups, force = _dense_parts(mode)
    raws, results, moved = [], [], []
    for limit in (D, 0):
        monkeypatch.setattr(hashagg, "_DENSE_SLOTS", limit)
        k = HashAggKernel(filt, groups, _dense_aggs(), force_hash=force)
        pending = k.dispatch(ch)
        raws.append(jax.device_get(pending))
        before = _dispatches()
        results.append(k.finalize(ch, pending))
        after = _dispatches()
        moved.append({p: after[p] - before[p] for p in after})
    return raws, results, moved


def _merged(aggs, res):
    agg = HashAggregator(aggs)
    agg.update(res)
    return agg.results()


def _assert_same_block(raws, results, moved, ch, mode, filt=None):
    (uniq, nuniq, coll, counts, rep, lanes, dense), \
        (uniq_s, nuniq_s, coll_s, counts_s, rep_s, lanes_s, dense_s) = raws
    # the choice follows the count, and the counter follows the choice
    assert bool(dense) == (int(nuniq) <= D) and not bool(dense_s)
    assert moved[0] == {"dense": int(bool(dense)),
                        "scatter": int(not bool(dense))}
    assert moved[1] == {"dense": 0, "scatter": 1}
    assert int(nuniq) == int(nuniq_s) and not coll and not coll_s
    # every lane, every slot but the masked one (capacity-1, never
    # live: its FIRST_ROW / representative fill is the row count under
    # a scatter and the empty segment's under a reduction)
    np.testing.assert_array_equal(uniq, uniq_s)
    np.testing.assert_array_equal(counts, counts_s)
    np.testing.assert_array_equal(rep[:-1], rep_s[:-1])
    for ls, ls_s in zip(lanes, lanes_s):
        for lane, lane_s in zip(ls, ls_s):
            assert lane.dtype == lane_s.dtype
            np.testing.assert_array_equal(lane[:-1], lane_s[:-1])
    aggs = _dense_aggs()
    _kt, groups, _force = _dense_parts(mode)
    host = _merged(aggs, host_hash_agg(ch, filt, groups, aggs))
    assert _merged(aggs, results[0]) == host
    assert _merged(aggs, results[1]) == host
    return int(nuniq), bool(dense)


@pytest.mark.parametrize("mode", ["direct", "cond", "hash"])
@pytest.mark.parametrize("null_keys,rows", [(True, _ROWS - 37),
                                            (False, _ROWS)],
                         ids=["nullkeys-padded", "exact-bucket"])
@pytest.mark.parametrize("slots", [D - 1, D, D + 1, None],
                         ids=["below", "at", "above", "one-group"])
def test_dense_equals_scatter_equals_host(monkeypatch, mode, null_keys,
                                          rows, slots):
    """`slots` is the count the table reports (the predicate's operand):
    the direct modes count the code domain (groups + NULL's code + the
    masked slot), the packed sort the distinct hashes plus the masked
    sentinel when a padding row exists."""
    if slots is None:
        groups = 1
    elif mode == "hash":
        groups = slots - int(null_keys) - int(rows < _ROWS)
    else:
        groups = slots - 2
    ch = _dense_chunk(mode, groups, rows, null_keys)
    raws, results, moved = _run_both(monkeypatch, mode, ch)
    nuniq, dense = _assert_same_block(raws, results, moved, ch, mode)
    if slots is not None:
        assert nuniq == slots and dense == (slots <= D)
    assert sum(int(c) for c in results[0].counts) == rows
    # the sums did pass 2^53, exactly
    assert max(int(v) for v in results[0].partials[0][0]) > 1 << 53


@pytest.mark.parametrize("mode", ["direct", "cond", "hash"])
@pytest.mark.parametrize("shape", ["all-masked", "empty-chunk"])
def test_dense_with_no_live_row(monkeypatch, mode, shape):
    ch = _dense_chunk(mode, 9, 300 if shape == "all-masked" else 0, True)
    filt = col(1, INT).lt(0) if shape == "all-masked" else None
    raws, results, moved = _run_both(monkeypatch, mode, ch, filt)
    nuniq, dense = _assert_same_block(raws, results, moved, ch, mode, filt)
    assert nuniq == 1 and dense        # the masked slot alone
    assert results[0].num_groups == 0 and results[1].num_groups == 0


@pytest.mark.parametrize("mode", ["direct", "cond", "hash"])
@pytest.mark.parametrize("groups,capacity,path", [
    (200, 64, "dense"), (D + 300, 512, "scatter")])
def test_capacity_error_on_both_branches(mode, groups, capacity, path):
    """More groups than the table holds: the count (what the choice
    reads too) still overshoots the capacity and finalize raises, with
    the dispatch counted under the branch that ran."""
    ch = _dense_chunk(mode, groups, 2048, False)
    _kt, gexprs, force = _dense_parts(mode)
    k = HashAggKernel(None, gexprs, _dense_aggs(), capacity=capacity,
                      force_hash=force)
    before = _dispatches()
    with pytest.raises(CapacityError) as e:
        k(ch)
    assert e.value.needed >= groups
    after = _dispatches()
    assert {p: after[p] - before[p] for p in after} == \
        {"dense": int(path == "dense"), "scatter": int(path == "scatter")}


# -- the root merge: columnar partials against the dict merge -----------------

from dataclasses import replace as _replace  # noqa: E402

from tidb_tpu.executor import _agg_chunk  # noqa: E402
from tidb_tpu.ops.hashagg import GroupResult  # noqa: E402
from tidb_tpu.plan.resolver import PlanSchema, SchemaCol  # noqa: E402

DT = st.new_datetime_field()
# value columns of every merge case: int, DECIMAL(15,2) (scaled), REAL,
# and a REAL of signed zeros (a MAX that python's max and numpy's
# maximum answer differently: -0.0 or 0.0)
_VALS = [INT, DEC2, DBL, DBL]
_KEY_FTS = {"int": [INT], "decimal": [DEC2], "datetime": [DT],
            "real": [DBL], "nullable": [INT], "two": [INT, DT],
            "scalar": []}


def _merge_aggs(nk: int):
    """Every columnar aggregate over the value columns after `nk` keys."""
    v_int, v_dec, v_dbl, v_zero = (col(nk + i, ft)
                                   for i, ft in enumerate(_VALS))
    return [AggDesc(AggFunc.COUNT, None), AggDesc(AggFunc.COUNT, v_dbl),
            AggDesc(AggFunc.SUM, v_dec), AggDesc(AggFunc.SUM, v_dbl),
            AggDesc(AggFunc.AVG, v_dec), AggDesc(AggFunc.AVG, v_dbl),
            AggDesc(AggFunc.MIN, v_int), AggDesc(AggFunc.MAX, v_dbl),
            AggDesc(AggFunc.MAX, v_zero), AggDesc(AggFunc.MIN, v_zero),
            AggDesc(AggFunc.FIRST_ROW, v_int),
            AggDesc(AggFunc.FIRST_ROW, v_zero)]


def _merge_chunk(rng, keys: str, rows: int):
    """`rows` rows: keys of the kind over a domain every partial shares
    (so groups overlap across partials), values with NULLs, negative
    decimals (AVG's half-up), and doubles whose sum depends on the
    order it is taken in."""
    fts = _KEY_FTS[keys]
    if keys == "two":
        key = [rng.integers(0, 4, rows),
               1_700_000_000_000_000 + 86_400_000_000 *
               rng.integers(0, 3, rows)]
    elif keys == "real":
        key = [rng.choice([-0.0, 0.0, 0.5, -2.25, 1e300], rows)]
    elif keys == "datetime":
        key = [1_700_000_000_000_000 + rng.integers(0, 9, rows)]
    elif keys == "decimal":
        key = [rng.integers(-900, 900, rows) * 7]
    elif keys != "scalar":
        key = [rng.integers(0, 12, rows)]
    else:
        key = []
    kvalid = [np.ones(rows, bool) for _ in key]
    if keys in ("nullable", "two"):
        kvalid[-1] = rng.random(rows) > 0.2
    vals = [rng.integers(-10**6, 10**6, rows),
            rng.integers(-10**7, 10**7, rows),
            rng.choice([0.1, 0.2, 0.3, 1e16, -1e16, -0.0, 2.5], rows),
            rng.choice([-0.0, 0.0, -1.0], rows)]
    vvalid = [rng.random(rows) > 0.15 for _ in vals]
    ch = Chunk.from_arrays(fts + _VALS, key + vals, kvalid + vvalid)
    return ch, [col(i, ft) for i, ft in enumerate(fts)]


def _partials(keys: str, nparts: int, seed: int):
    """Host partials of `nparts` chunks over one key domain; the second
    of several is empty (its filter keeps no row)."""
    rng = np.random.default_rng(seed)
    nk = len(_KEY_FTS[keys])
    aggs = _merge_aggs(nk)
    out = []
    for i in range(nparts):
        ch, groups = _merge_chunk(rng, keys, int(rng.integers(1, 300)))
        filt = col(nk, INT).lt(-10**7) if i == 1 else None
        out.append(host_hash_agg(ch, filt, groups, aggs))
    return aggs, [g.ft for g in groups], out


def _dict_merger(aggs, meta=None):
    """A HashAggregator held to the dict merge (the reference)."""
    agg = HashAggregator(aggs, meta)
    agg._cols = None
    return agg


def _chunk_of(agg, aggs, key_fts):
    schema = PlanSchema([SchemaCol(f"c{i}", "", ft) for i, ft in
                         enumerate(key_fts + [a.result_ft for a in aggs])])
    return _agg_chunk(schema, len(key_fts), aggs, agg, note=False)


def _assert_same_merge(aggs, key_fts, parts):
    got, ref = HashAggregator(aggs, key_fts), _dict_merger(aggs, key_fts)
    for p in parts:
        got.update(p)
        ref.update(p)
    assert (got.path, ref.path) == ("columnar", "dict")
    want = ref.results()
    assert got.results() == want
    # every value exact, of one kind (no float where the dict has an
    # int) and of one sign (-0.0 is not 0.0 here)
    for (gk, gv), (wk, wv) in zip(got.results(), want):
        assert [_exact(x) for x in gk + tuple(gv)] == \
            [_exact(x) for x in wk + tuple(wv)]
    a, b = _chunk_of(got, aggs, key_fts), _chunk_of(ref, aggs, key_fts)
    assert a.num_rows == b.num_rows == max(len(want), int(not key_fts))
    for ca, cb in zip(a.columns, b.columns):
        assert ca.data.dtype == cb.data.dtype
        np.testing.assert_array_equal(ca.valid, cb.valid)
        assert [_exact(x) for x in ca.data.tolist()] == \
            [_exact(x) for x in cb.data.tolist()]
    return want


def _exact(x):
    if isinstance(x, (float, np.floating)):
        return float, float(x), np.signbit(x)
    return type(x) is float, x


@pytest.mark.parametrize("keys", list(_KEY_FTS))
@pytest.mark.parametrize("nparts", [1, 2, 5])
def test_columnar_merge_equals_dict_merge(keys, nparts):
    aggs, key_fts, parts = _partials(keys, nparts, seed=nparts * 31)
    want = _assert_same_merge(aggs, key_fts, parts)
    if keys != "scalar":
        # rows in key order, NULL last in each key part
        order = [tuple((x is None, x) for x in k) for k, _v in want]
        assert order == sorted(order) and len(set(order)) == len(order)
    if keys == "real":
        # -0.0 and 0.0 are one group, under the first-seen variant
        assert sum(k == (0.0,) for k, _v in want) == 1


@pytest.mark.parametrize("keys", ["int", "two", "scalar"])
def test_columnar_merge_folds_in_arrival_order(monkeypatch, keys):
    """Partials folded a few at a time (the held partials' bound) give
    the one-pass merge's rows, bit for bit."""
    aggs, key_fts, parts = _partials(keys, 9, seed=7)
    monkeypatch.setattr(hashagg, "_FOLD_PARTS", 2)
    _assert_same_merge(aggs, key_fts, parts)


def test_columnar_merge_of_device_partials():
    """Partials the device kernel built (keys evaluated on the
    representative rows) merge as the host's do."""
    rng = np.random.default_rng(5)
    aggs, key_fts, parts = _partials("two", 1, seed=3)
    groups = [col(i, ft) for i, ft in enumerate(key_fts)]
    k = HashAggKernel(None, groups, aggs)
    for _ in range(3):
        ch, _g = _merge_chunk(rng, "two", 400)
        parts.append(k(ch))
    _assert_same_merge(aggs, key_fts, parts)


def test_columnar_merge_empty_and_scalar_over_nothing():
    aggs, key_fts, _parts = _partials("int", 1, seed=1)
    agg = HashAggregator(aggs, key_fts)
    agg.update(GroupResult(keys=[(np.zeros(0, np.int64), np.zeros(0, bool))],
                           partials=[[] for _ in aggs],
                           counts=np.zeros(0, np.int64)))
    assert agg.path == "columnar" and agg.results() == []
    assert _chunk_of(agg, aggs, key_fts).num_rows == 0
    # a scalar aggregate over no rows still answers one row
    out = _chunk_of(HashAggregator(aggs), aggs, [])
    assert out.num_rows == 1 and out.columns[0].data.tolist() == [0]
    assert not out.columns[2].valid[0]


def _final_groups():
    snap = metrics.snapshot()
    return {p: snap.get('tidb_tpu_agg_final_groups_total{path="%s"}' % p,
                        0) for p in ("columnar", "dict")}


STR_CI = _replace(STR, collation="utf8mb4_general_ci")


@pytest.mark.parametrize("case,path", [
    ("int", "columnar"), ("string", "dict"), ("ci", "dict"),
    ("group_concat", "dict"), ("mixed_lanes", "dict")])
def test_root_merge_routing(case, path):
    """The merge follows what the partials show: numeric keys and lanes
    merge as columns; string and _ci keys, GROUP_CONCAT and partials
    whose lane dtypes differ take the dict, with the same answer."""
    kft = {"string": STR, "ci": STR_CI}.get(case, INT)
    rows = [("Ab" if kft is not INT else 1, 1), ("aB" if kft is not INT
                                                  else 2, 2),
            (None, 3), ("ab" if kft is not INT else 1, 4)]
    ch = Chunk.from_rows([kft, INT], rows)
    aggs = [AggDesc(AggFunc.SUM, col(1, INT)), AggDesc(AggFunc.COUNT, None)]
    if case == "group_concat":
        aggs.append(AggDesc(AggFunc.GROUP_CONCAT, col(1, INT)))
    parts = [host_hash_agg(ch, None, [col(0, kft)], aggs) for _ in range(2)]
    if case == "mixed_lanes":
        p = parts[1]
        parts[1] = _replace(p, partials=[[p.partials[0][0].astype(float),
                                          p.partials[0][1]],
                                         p.partials[1]])
    agg, ref = HashAggregator(aggs, [kft]), _dict_merger(aggs, [kft])
    for p in parts:
        agg.update(p)
        ref.update(p)
    assert agg.path == path
    assert agg.results() == ref.results()
    before = _final_groups()
    out = _chunk_of(agg, aggs, [kft])
    schema = PlanSchema([SchemaCol(f"c{i}", "", c.ft)
                         for i, c in enumerate(out.columns)])
    _agg_chunk(schema, 1, aggs, agg)
    after = _final_groups()
    moved = {p: after[p] - before[p] for p in after}
    assert moved == {path: out.num_rows,
                     ({"columnar", "dict"} - {path}).pop(): 0}


def _tuple_form_bytes(gr) -> int:
    """memtrack.result_bytes as it counted a partial whose keys were
    tuples: 8 bytes a key part a group (8 for a scalar partial's one),
    a string key's length, the lanes' and counts' nbytes."""
    total = gr.counts.nbytes + sum(np.asarray(lane).nbytes
                                   for ls in gr.partials for lane in ls)
    for key in gr.key_tuples():
        total += 8 * max(len(key), 1)
        total += sum(len(x) for x in key if isinstance(x, (str, bytes)))
    return total


@pytest.mark.parametrize("keys", ["int", "two", "string", "scalar",
                                  "empty"])
def test_result_bytes_of_key_columns_as_of_tuples(keys):
    """A columnar partial is billed what its tuple form was, so the
    streamed frame cap (store/stream.py) decides as it did."""
    from tidb_tpu import memtrack
    rows = [(i % 7, None if i % 5 == 0 else "x" * (i % 4), i)
            for i in range(60)]
    ch = Chunk.from_rows([INT, STR, INT], rows)
    groups = {"int": [col(0, INT)], "string": [col(1, STR)],
              "two": [col(0, INT), col(1, STR)], "scalar": [],
              "empty": [col(0, INT)]}[keys]
    filt = col(2, INT).lt(0) if keys == "empty" else None
    aggs = [AggDesc(AggFunc.SUM, col(2, INT)), AggDesc(AggFunc.COUNT, None)]
    gr = host_hash_agg(ch, filt, groups, aggs)
    assert memtrack.result_bytes(gr) == _tuple_form_bytes(gr)


def test_result_bytes_of_a_q18_block():
    """TPC-H Q18's inner partial of one resident block: 112,500 groups
    of one int64 key, a DECIMAL SUM's two lanes and the counts — 32
    bytes a group, 3.6 MB, under the 4 MiB stream frame."""
    from tidb_tpu import memtrack
    n = 112_500
    gr = GroupResult(keys=[(np.arange(n, dtype=np.int64),
                            np.ones(n, dtype=bool))],
                     partials=[[np.zeros(n, np.int64), np.ones(n, np.int64)]],
                     counts=np.ones(n, np.int64))
    assert memtrack.result_bytes(gr) == _tuple_form_bytes(gr) == 32 * n
    assert 32 * n < 4 << 20
