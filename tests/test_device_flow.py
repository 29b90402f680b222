"""The device-plane dataflow pass (tidb_tpu/lint/flow/device.py):
discovery of every traced-program construction site across its four
forms, dispatch resolution, the static compile-prediction contract and
its cross-check against the profiler plane on a warm TPC-H run, and
the runtime pin for the audited `donate_argnums` sites in ops/hashagg
and ops/streamagg (ISSUE 20's donation audit: the donating branch
returns at the dispatch, the donated transfer skips the chunk memo,
and the non-donating twin re-transfers from host afterwards)."""

import random
import warnings

import pytest

from tidb_tpu.lint.engine import Forest
from tidb_tpu.lint.flow.device import DeviceFlow, device_flow_of


@pytest.fixture(scope="module")
def df():
    return device_flow_of(Forest.load())


# -- discovery --------------------------------------------------------------

def test_discovers_all_construction_forms(df):
    forms = {s.form for s in df.sites}
    assert forms == {"jit", "plane_jit"}
    stores = {s.store[0] for s in df.sites}
    # instance attrs, bucket dicts, module globals, factory returns
    # and locals
    assert {"attr", "dict", "global", "return", "local"} <= stores


def test_discovers_the_partial_decorator_form():
    # no kernel in the tree is built this way any more; the form stays
    # discoverable for the next one
    src = ("import functools\nimport jax\n\n\n"
           "@functools.partial(jax.jit, static_argnums=(1,),\n"
           "                   donate_argnums=(0,))\n"
           "def k(x, n):\n    return x\n")
    flow = device_flow_of(Forest.from_sources(
        {"tidb_tpu/ops/k.py": src}, root=None))
    [site] = flow.sites
    assert (site.form, site.store) == ("partial_jit", ("decorator", "k"))
    assert site.static_nums == (1,) and site.donate == (0,)


def test_discovers_the_known_kernel_sites(df):
    by_rel = {}
    for s in df.sites:
        by_rel.setdefault(s.rel, []).append(s)
    assert len(by_rel["tidb_tpu/ops/hashagg.py"]) == 4    # _jit/_jitd x2
    assert len(by_rel["tidb_tpu/ops/streamagg.py"]) == 2  # _jit/_jitd
    assert len(by_rel["tidb_tpu/ops/meshjoin.py"]) == 3   # 3 stages


def test_donating_sites_are_exactly_the_jitd_twins(df):
    donating = sorted((s.rel, s.store[1]) for s in df.sites
                      if s.donating)
    assert donating == [("tidb_tpu/ops/hashagg.py", "_jitd"),
                        ("tidb_tpu/ops/hashagg.py", "_jitd"),
                        ("tidb_tpu/ops/streamagg.py", "_jitd")]
    for s in df.sites:
        if s.donating:
            assert s.donate == (0,)     # the padded input columns


def test_traced_bodies_resolve_through_owner_classes(df):
    names = set()
    for s in df.sites:
        names |= {f.qualname for f in s.fns}
    assert "HashAggKernel._kernel" in names
    assert "SegmentAggKernel._kernel" in names
    # factory-returns-nested-def and shard_map unwrapping
    assert "MeshLookupAggKernel._stage2_fn.<locals>.stage2" in names
    assert "MeshShuffleJoinKernel._program.<locals>.kernel" in names


def test_dispatches_resolve_to_sites(df):
    assert len(df.dispatches) >= 10
    donating = [d for d in df.dispatches if d.site.donating]
    assert len(donating) == 3
    # the bucketed factory-call-then-call shape is classified with its
    # inner factory call attached (the memo-key check's input)
    assert any(d.via_factory is not None for d in df.dispatches)


def test_memoized_on_forest(df):
    forest = Forest.load()
    a = device_flow_of(forest)
    assert device_flow_of(forest) is a
    assert isinstance(a, DeviceFlow)


# -- compile predictions ----------------------------------------------------

def test_compile_predictions_cover_every_profiler_family(df):
    from tidb_tpu import profiler
    preds = df.compile_predictions()
    assert set(preds) == set(profiler.FAMILIES)
    for fam, p in preds.items():
        assert p["warm_growth"] == 0
        if fam == "plane":
            # bucket dicts construct one program per pow2 bucket and
            # kernel instance: no static per-row bound
            assert p["per_row_bound"] is None
        else:
            assert p["per_row_bound"] == 1
    assert preds["plane"]["sites"] == sum(
        1 for s in df.sites if s.form == "plane_jit")


def test_warm_run_stays_inside_the_compile_predictions(df):
    """The static model against the profiler plane, both directions:
    every family that compiled on warm TPC-H Q1/Q3 has a prediction
    (else the device pass fell behind the runtime), no family compiles
    on a warm iteration and no fingerprinted row compiles past its
    per-row bound (else the runtime fell behind the contract the lint
    rules enforce)."""
    import tpch
    from tidb_tpu import config, profiler
    from tidb_tpu.session import Session
    from tidb_tpu.store.storage import new_mock_storage

    preds = df.compile_predictions()
    s = Session(new_mock_storage())
    s.execute("CREATE DATABASE tpch_flow")
    s.execute("USE tpch_flow")
    tpch.load(s, tpch.TpchData(seed=5))

    def family_compiles() -> dict:
        fams: dict = {}
        for p in profiler.snapshot():
            fams[p["family"]] = fams.get(p["family"], 0) + p["compiles"]
        return fams

    profiler.reset_for_tests()
    try:
        with config.session_overlay({"tidb_tpu_device": 1,
                                     "tidb_tpu_device_min_rows": 1}):
            for sql in (tpch.Q1, tpch.Q3):
                s.query(sql)            # cold: compile + cache fill
            cold = family_compiles()
            for _ in range(2):
                for sql in (tpch.Q1, tpch.Q3):
                    s.query(sql)
        warm = family_compiles()
        assert warm, "no family compiled anything: nothing was checked"
        assert set(warm) <= set(preds)
        for fam, n in warm.items():
            assert n - cold.get(fam, 0) <= preds[fam]["warm_growth"], \
                (fam, cold, warm)
        over = [(p["family"], p["fingerprint"][:16], p["compiles"])
                for p in profiler.snapshot()
                if preds[p["family"]]["per_row_bound"] is not None
                and not p["fingerprint"].startswith("~")
                and p["compiles"] > preds[p["family"]]["per_row_bound"]]
        assert not over, over
    finally:
        s.close()
        profiler.reset_for_tests()


# -- donation audit (ISSUE 20 satellite): runtime pin -----------------------

def _mk_kernel_and_chunks():
    from tidb_tpu import sqltypes as st
    from tidb_tpu.chunk import Chunk
    from tidb_tpu.expression import AggDesc, AggFunc, col
    from tidb_tpu.ops.hashagg import HashAggKernel

    INT = st.new_int_field()
    rng = random.Random(7)
    rows = [(rng.randrange(6), rng.randrange(50)) for _ in range(500)]
    k = HashAggKernel(None, [col(0, INT)],
                      [AggDesc(AggFunc.SUM, col(1, INT)),
                       AggDesc(AggFunc.COUNT, None)])
    return (k, Chunk.from_rows([INT, INT], rows),
            Chunk.from_rows([INT, INT], rows))


def _result_map(k, res):
    from tidb_tpu.ops.hashagg import HashAggregator
    agg = HashAggregator(k.aggs)
    agg.update(res)
    return {key[0]: tuple(v) for key, v in agg.results()}


def test_hashagg_donating_dispatch_skips_memo_and_matches(monkeypatch):
    """The audited `_jitd` sites: with donation forced on, the
    donating branch must (a) produce the same result as the plain
    twin, (b) skip the chunk device memo (a memoized donated buffer is
    read-after-free), and (c) leave the chunk re-dispatchable through
    the NON-donating twin afterwards — the fresh host transfer, not
    the donated buffer, feeds the second dispatch."""
    from tidb_tpu.ops import runtime
    monkeypatch.setattr(runtime, "_donation_supported", True)
    k, ch_plain, ch_don = _mk_kernel_and_chunks()
    size = runtime.bucket_size(ch_don.num_rows)

    with warnings.catch_warnings():
        # CPU backends warn that donated buffers were unusable; the
        # dispatch path under test is identical either way
        warnings.simplefilter("ignore")
        plain = _result_map(k, k.finalize(
            ch_plain, k.dispatch(ch_plain, donate=False)))
        assert runtime.dev_cache_get(ch_plain, size) is not None

        donated = _result_map(k, k.finalize(
            ch_don, k.dispatch(ch_don, donate=True)))
        assert k._jitd is not None          # lazy twin materialized
        assert runtime.dev_cache_get(ch_don, size) is None

        again = _result_map(k, k.finalize(
            ch_don, k.dispatch(ch_don, donate=False)))

    assert donated == plain
    assert again == plain


def test_streamagg_donating_dispatch_skips_memo(monkeypatch):
    from tidb_tpu import sqltypes as st
    from tidb_tpu.chunk import Chunk
    from tidb_tpu.expression import AggDesc, AggFunc, col
    from tidb_tpu.ops import runtime
    from tidb_tpu.ops.streamagg import SegmentAggKernel

    monkeypatch.setattr(runtime, "_donation_supported", True)
    INT = st.new_int_field()
    rows = [(i // 5, i % 7) for i in range(200)]
    ch = Chunk.from_rows([INT, INT], rows)
    k = SegmentAggKernel([col(0, INT)],
                         [AggDesc(AggFunc.SUM, col(1, INT))])
    size = runtime.bucket_size(ch.num_rows)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        pending = k.dispatch(ch, donate=True)
        res = k.finalize(ch, pending)
    assert runtime.dev_cache_get(ch, size) is None
    assert res is not None
