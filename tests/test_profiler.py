"""Kernel profiling plane (tidb_tpu/profiler.py): the bounded
per-(family, fingerprint, mesh) registry, its memtrack billing + shed
drain, mesh-aware keying at plane sizes 1 and 8, the roofline
estimator, the EXPLAIN ANALYZE / information_schema surfaces, the
per-digest mode-history memo, and the disarmed fast path's overhead
budget."""

import time

import pytest

import tpch
from tidb_tpu import config, devplane, memtrack, perfschema, profiler, sched
from tidb_tpu.session import Session
from tidb_tpu.store.storage import new_mock_storage

_ENTRY = profiler._ENTRY_BYTES


@pytest.fixture(autouse=True)
def _clean_registry():
    profiler.reset_for_tests()
    yield
    profiler.reset_for_tests()


class TestRegistry:
    def test_same_key_returns_same_entry(self):
        a = profiler.profile("hashagg", "fp-1")
        b = profiler.profile("hashagg", "fp-1")
        assert a is b
        assert profiler.profile("hashagg", "fp-2") is not a
        assert profiler.profile("streamagg", "fp-1") is not a

    def test_compile_vs_reuse_attribution(self):
        prof = profiler.profile("hashagg", "fp-c")
        profiler.note_construct(prof, reuse=False)
        # the first dispatch of a fresh entry is the compile dispatch
        profiler.note_dispatch(prof, 5_000, nbytes=1024)
        profiler.note_dispatch(prof, 1_000, nbytes=1024)
        profiler.note_construct(prof, reuse=True)
        d = prof.to_dict()
        assert d["compiles"] == 1 and d["reuses"] == 1
        assert d["dispatches"] == 2
        assert d["compile_ns"] == 5_000       # only the compile dispatch
        assert d["busy_ns"] == 6_000
        assert d["bytes_in"] == 2048
        assert d["compile_cache"] in ("hit", "miss", "cached")

    def test_precompiled_executable_attributes_reuse(self):
        # a dispatch through a profile row that never witnessed the
        # compile (the executable predates the row — e.g. the registry
        # was shed and the kernel re-registered) attributes "reuse"
        prof = profiler.profile("hashagg", "fp-r")
        profiler.note_construct(prof, reuse=True)
        profiler.note_dispatch(prof, 1_000, nbytes=512)
        d = prof.to_dict()
        assert d["compiles"] == 0 and d["compile_ns"] == 0
        assert d["compile_cache"] == "reuse"
        # a later real compile overwrites the placeholder
        profiler.note_construct(prof, reuse=False)
        profiler.note_dispatch(prof, 2_000, nbytes=512)
        assert prof.to_dict()["compile_cache"] in ("hit", "miss", "cached")

    def test_escalations_and_fallback_reasons(self):
        prof = profiler.profile("fragment", "fp-e")
        profiler.note_escalation(prof)
        profiler.note_kernel_fallback(prof, "capacity")
        profiler.note_kernel_fallback(prof, "capacity")
        profiler.note_kernel_fallback(prof, "unsupported")
        d = prof.to_dict()
        assert d["escalations"] == 1
        assert d["fallbacks"] == 3
        assert d["fallback_reasons"] == {"capacity": 2, "unsupported": 1}

    def test_long_fingerprints_are_bounded(self):
        prof = profiler.profile("mesh", "x" * 500)
        assert len(prof.fingerprint) == 16

    def test_lru_bound_and_eviction(self):
        old = config.get_var("tidb_tpu_kernel_profile_cap")
        config.set_var("tidb_tpu_kernel_profile_cap", 16)
        try:
            for i in range(24):
                profiler.profile("hashagg", f"fp-{i}")
            reg = profiler.registry()
            assert len(reg) == 16
            st = reg.stats()
            assert st["evictions"] == 8 and st["cap"] == 16
            # LRU, not FIFO: the oldest surviving entries are the
            # youngest 16 created
            fps = {p["fingerprint"] for p in profiler.snapshot()}
            assert fps == {f"fp-{i}" for i in range(8, 24)}
        finally:
            config.set_var("tidb_tpu_kernel_profile_cap", old)

    def test_disabled_profiling_returns_none(self):
        old = config.get_var("tidb_tpu_kernel_profile")
        config.set_var("tidb_tpu_kernel_profile", 0)
        try:
            assert profiler.profile("hashagg", "fp") is None
            # every note_* is None-tolerant (call sites stay unguarded)
            profiler.note_construct(None, reuse=True)
            profiler.note_dispatch(None, 100)
            profiler.note_busy(None, 100)
            profiler.note_bytes(None, nbytes=10)
            profiler.note_escalation(None)
            profiler.note_kernel_fallback(None, "x")
            with profiler.dispatch_section(None, nbytes=1):
                pass
            assert not profiler.stats()["enabled"]
        finally:
            config.set_var("tidb_tpu_kernel_profile", old)

    def test_dispatch_section_success_only(self):
        prof = profiler.profile("hashagg", "fp-s")
        with pytest.raises(ValueError):
            with profiler.dispatch_section(prof, nbytes=512):
                raise ValueError("dispatch blew up")
        assert prof.to_dict()["dispatches"] == 0
        with profiler.dispatch_section(prof, nbytes=512) as sec:
            sec.out_nbytes = 64
        d = prof.to_dict()
        assert d["dispatches"] == 1 and d["bytes_out"] == 64


class TestMemtrackBilling:
    pytestmark = pytest.mark.usefixtures("ledger_hygiene")

    def test_entries_billed_and_clear_releases(self):
        reg = profiler.registry()
        node = reg._billing_node()
        base = node.host
        for i in range(10):
            profiler.profile("hashagg", f"bill-{i}")
        assert node.host == base + 10 * _ENTRY
        reg.clear()
        assert node.host == base

    def test_eviction_releases_bytes(self):
        old = config.get_var("tidb_tpu_kernel_profile_cap")
        config.set_var("tidb_tpu_kernel_profile_cap", 16)
        try:
            node = profiler.registry()._billing_node()
            base = node.host
            for i in range(40):
                profiler.profile("hashagg", f"ev-{i}")
            # evicted entries gave their bytes back: only cap remain
            assert node.host == base + 16 * _ENTRY
        finally:
            config.set_var("tidb_tpu_kernel_profile_cap", old)
            profiler.reset_for_tests()

    def test_shed_chain_drains_registry(self):
        for i in range(8):
            profiler.profile("fragment", f"shed-{i}")
        assert len(profiler.registry()) == 8
        # the administrative shed (GET /shed, admission pressure) runs
        # every registered spill action — profile history must drop
        sched.shed_server(0)
        assert len(profiler.registry()) == 0
        assert profiler.registry()._billing_node().host == 0


class TestMeshKeying:
    @pytest.mark.parametrize("n", (1, 8), ids=["plane1", "plane8"])
    def test_rows_keyed_by_mesh(self, n):
        if n > 1:
            devplane.enable_mesh(n)
        try:
            prof = profiler.profile("hashagg", "mesh-key")
            assert prof.mesh == devplane.mesh_fingerprint(process=True)
        finally:
            if n > 1:
                devplane.disable_mesh()

    def test_topology_change_starts_fresh_rows(self):
        p1 = profiler.profile("hashagg", "mesh-key")
        devplane.enable_mesh(8)
        try:
            p8 = profiler.profile("hashagg", "mesh-key")
            assert p8 is not p1
            assert p8.mesh != p1.mesh
        finally:
            devplane.disable_mesh()
        # back at plane 1 the original row resumes (same key again)
        assert profiler.profile("hashagg", "mesh-key") is p1


class TestRoofline:
    def test_platform_peak_is_cached_and_positive(self):
        g1, src1 = profiler.platform_peak_gbps()
        g2, src2 = profiler.platform_peak_gbps()
        assert g1 > 0 and (g1, src1) == (g2, src2)
        # CPU CI: measured memcpy; chip: datasheet lookup
        assert src1.startswith(("datasheet(", "measured-memcpy("))

    def test_fraction_math(self):
        peak, _src = profiler.platform_peak_gbps()
        # exactly peak bandwidth -> fraction 1.0
        nbytes = int(peak * 1e9)
        assert profiler.achieved_gbps(nbytes, int(1e9)) == \
            pytest.approx(peak)
        assert profiler.roofline_fraction(nbytes, int(1e9)) == \
            pytest.approx(1.0)
        assert profiler.achieved_gbps(0, 100) is None
        assert profiler.roofline_fraction(100, 0) is None


class TestOverheadDisarmed:
    def test_disarmed_per_statement_overhead_is_tiny(self):
        """With tidb_tpu_kernel_profile off, the profiler's footprint
        on a statement is one config read returning None plus
        None-tolerant note_* early exits. Budget <5us per statement
        (same bar as the trace subsystem's disarmed pin)."""
        old = config.get_var("tidb_tpu_kernel_profile")
        config.set_var("tidb_tpu_kernel_profile", 0)
        try:
            n = 20_000
            t0 = time.perf_counter()
            for _ in range(n):
                prof = profiler.profile("hashagg", "overhead")
                profiler.note_construct(prof, reuse=True)
                with profiler.dispatch_section(prof, nbytes=4096):
                    pass
                profiler.note_dispatch(prof, 100, plan=None)
            per_stmt = (time.perf_counter() - t0) / n
            assert len(profiler.registry()) == 0    # truly disarmed
            assert per_stmt < 5e-6, \
                f"{per_stmt * 1e6:.2f}us per statement"
        finally:
            config.set_var("tidb_tpu_kernel_profile", old)


@pytest.fixture(scope="module")
def sess():
    s = Session(new_mock_storage())
    s.execute("CREATE DATABASE tpch")
    s.execute("USE tpch")
    tpch.load(s, tpch.TpchData(seed=7))
    yield s
    s.close()


class TestEndToEnd:
    def test_warm_q1_explain_analyze_kernel_note(self, sess):
        profiler.reset_for_tests()
        with config.session_overlay({"tidb_tpu_device": 1}):
            sess.query(tpch.Q1)                      # warm the caches
            r = sess.query("EXPLAIN ANALYZE " + tpch.Q1)
        assert r.columns[-1] == "kernel"
        cells = [row[-1] for row in r.rows if row[-1] != "-"]
        assert cells, r.rows
        # family + compile attribution + mode on the operator that
        # dispatched; roofline only when bytes were billed
        note = cells[0]
        assert "agg" in note
        assert "compile=" in note and "mode=" in note

    def test_kernel_profile_memtable_row(self, sess):
        profiler.reset_for_tests()
        with config.session_overlay({"tidb_tpu_device": 1}):
            for _ in range(2):
                sess.query(tpch.Q1)
        rows = sess.query(
            "SELECT family, compiles, dispatches, busy_ns, "
            "roofline_fraction FROM information_schema.kernel_profile"
        ).rows
        assert rows, "kernel_profile unpopulated after warm Q1"
        fam, compiles, dispatches, busy_ns, roof = rows[0]
        assert fam in profiler.FAMILIES
        assert dispatches >= 1 and busy_ns > 0
        # a warm second run must not recompile
        assert compiles <= 1

    def test_warm_sweep_compiles_flat_and_every_row_has_a_roofline(
            self, sess):
        """Warm Q1/Q3/Q5 under the profiler: compile counts stay flat
        across warm iterations, every kernel_profile row that moved
        bytes carries a roofline_fraction, and every statement_profile
        memo row carries the mode that ran."""
        profiler.reset_for_tests()
        perfschema.memo_reset()

        def total_compiles() -> int:
            return sum(p["compiles"] for p in profiler.snapshot())

        with config.session_overlay({"tidb_tpu_device": 1}):
            for sql in (tpch.Q1, tpch.Q3, tpch.Q5):
                sess.query(sql)         # cold: compile + cache fill
            track = []
            for _ in range(2):
                for sql in (tpch.Q1, tpch.Q3, tpch.Q5):
                    sess.query(sql)
                track.append(total_compiles())
        assert track[-1] == track[0], track
        rows = sess.query(
            "SELECT family, dispatches, bytes_in, roofline_fraction "
            "FROM information_schema.kernel_profile").rows
        assert any(r[1] for r in rows), rows
        assert not [r[0] for r in rows
                    if r[1] and r[2] and r[3] is None], rows
        memo = sess.query("SELECT digest, op, mode FROM "
                          "information_schema.statement_profile").rows
        assert memo and all(m[2] for m in memo), memo

    def test_mode_memo_after_cardinality_sweep(self, sess):
        perfschema.memo_reset()
        with config.session_overlay({"tidb_tpu_device": 1}):
            # one digest, two observed cardinalities (literal stripped:
            # both WHERE bounds normalize into the same digest)
            sess.query("SELECT l_returnflag, COUNT(*) FROM lineitem "
                       "WHERE l_orderkey < 100 GROUP BY l_returnflag")
            sess.query("SELECT l_returnflag, COUNT(*) FROM lineitem "
                       "WHERE l_orderkey < 600 GROUP BY l_returnflag")
        memo = sess.query(
            "SELECT digest, op, mode, runs, last_groups, max_groups "
            "FROM information_schema.statement_profile").rows
        assert memo, "memo unpopulated"
        by_digest = {}
        for dg, op, mode, runs, last_g, max_g in memo:
            assert mode in ("direct", "hash", "sort", "fused",
                            "hybrid", "host")
            by_digest.setdefault(dg, []).append((op, runs, last_g,
                                                 max_g))
        # the swept digest folded both runs into one memo row
        assert any(sum(r for _op, r, _l, _m in rows) >= 2
                   for rows in by_digest.values()), memo
        assert all(max_g >= last_g >= 0
                   for rows in by_digest.values()
                   for _op, _r, last_g, max_g in rows)

    def test_memo_is_bounded(self, sess):
        perfschema.memo_reset()
        old = config.get_var("tidb_tpu_stmt_profile_cap")
        config.set_var("tidb_tpu_stmt_profile_cap", 16)
        try:
            for i in range(24):
                perfschema.memo_record(f"digest-{i}", [
                    {"name": "TableReader", "mode": "hash",
                     "act_rows": i, "device_time_ns": 10}])
            assert len(perfschema.memo_snapshot()) == 16
        finally:
            config.set_var("tidb_tpu_stmt_profile_cap", old)
            perfschema.memo_reset()

    def test_status_doc_carries_profiler_state(self, sess):
        from tidb_tpu import member
        doc = member.local_state()
        assert "kernel_profile" in doc
        st = profiler.stats()
        assert set(st) >= {"entries", "cap", "evictions", "compiles",
                           "dispatches", "busy_ns", "enabled"}
