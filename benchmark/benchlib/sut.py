"""The system under test, and the only module that imports it.

Brings the server up through the program's own entry point
(`tidb_tpu.__main__.start`, what `python -m tidb_tpu` runs) at default
sysvars, bulk-loads generated tables through the program's offline
import, and reads the program's counters the way an operator would:
GET /status on the status port, `performance_schema` over the wire, and
the two cache objects' public counters.
"""

from __future__ import annotations

import json
import time
import urllib.request

DIGEST_SQL = ("SELECT digest_text, exec_count, sum_latency_ns, "
              "sum_parse_ns, sum_plan_ns, sum_exec_ns FROM "
              "performance_schema.events_statements_summary_by_digest")


class Sut:
    def __init__(self, chips: int):
        from tidb_tpu.__main__ import start
        self.chips = chips
        self.running = start(port=0, status_port=0, mesh=chips)
        self.port = self.running.server.port
        self._closed = False

    # -- set-up -------------------------------------------------------------

    def load(self, database: str, gen, data, tables, regions: int,
             chunk_rows: int = 16384) -> dict:
        """CREATE DATABASE, then DDL + bulk import + region pre-split of
        `tables` only. -> {"rows", "seconds"}. The import goes through the
        program's `bulk_load` in slices of `chunk_rows` rows: it encodes a
        string column one distinct value at a time against the whole
        slice, which is quadratic in the slice for a comment column."""
        from tidb_tpu.session import Session
        from tidb_tpu.table import Table, bulkload
        storage = self.running.storage
        t0 = time.perf_counter()
        session = Session(storage)
        try:
            session.execute(f"CREATE DATABASE {database}")
            session.execute(f"USE {database}")
            for t in tables:
                session.execute(gen.ddl(t))
            ischema = session.domain.info_schema()
            rows = 0
            for t in tables:
                info = ischema.table(database, t)
                table = Table(info, storage)
                cols = gen.columns(data, t)
                handles = gen.handles(t, data)
                n = len(next(iter(cols.values())))
                for lo in range(0, n, chunk_rows):
                    hi = min(lo + chunk_rows, n)
                    rows += bulkload.bulk_load(
                        storage, table,
                        {k: v[lo:hi] for k, v in cols.items()},
                        handles=None if handles is None else handles[lo:hi])
                del cols
                top = gen.split(t, data)
                if top is not None and regions > 1:
                    storage.cluster.split_table(info.id, regions,
                                                max_handle=top)
        finally:
            session.close()
        return {"rows": rows, "seconds": time.perf_counter() - t0}

    def stats_version(self) -> int:
        """The statistics handle's version: every ANALYZE saved and every
        histogram feedback bumps it, and a cached plan is keyed by it."""
        from tidb_tpu.session import Domain
        return Domain.get(self.running.storage).stats_handle().version

    def stats_pass(self) -> dict:
        """One beat of the program's stats worker, run now: the call the
        worker's tick makes (`Domain.auto_analyze_tick`), which analyzes
        every table whose modifications crossed the auto-analyze ratio.
        The worker itself runs on: its next tick finds what this one
        analyzed no longer pending. -> {"analyzed": ["db.table", ...],
        "version_before", "version_after"}."""
        from tidb_tpu.session import Domain
        domain = Domain.get(self.running.storage)
        before = domain.stats_handle().version
        done = domain.auto_analyze_tick()
        ischema = domain.info_schema()
        names = ["%s.%s" % (db, info.name)
                 for db, info in map(ischema.table_by_id, done)]
        return {"analyzed": names, "version_before": before,
                "version_after": domain.stats_handle().version}

    # -- counters -----------------------------------------------------------

    def snapshot(self, client) -> dict:
        """One reading of every counter a per-layer reader may diff.
        `client` is a wire connection of the benchmark's own."""
        import jax
        url = f"http://127.0.0.1:{self.running.status.port}/status"
        with urllib.request.urlopen(url, timeout=60) as r:
            st = json.load(r)
        storage = self.running.storage
        cc = storage.chunk_cache
        _cols, rows = client.query(DIGEST_SQL)
        digests = {r[0]: {"exec_count": int(r[1]), "latency_ns": int(r[2]),
                          "parse_ns": int(r[3]), "plan_ns": int(r[4]),
                          "exec_ns": int(r[5])} for r in rows}
        mem = [d.memory_stats() or {} for d in jax.devices()[:self.chips]]
        return {
            "at": time.perf_counter(),
            "metrics": st["metrics"],
            "serving": st["serving"],
            "compile_cache": st["compile_cache"],
            "chunk_cache": {"hits": cc.hits, "misses": cc.misses},
            "hbm_resident_bytes": storage.device_cache.resident_bytes(),
            "stats_version": self.stats_version(),
            "digests": digests,
            "memory": [{"bytes_in_use": m.get("bytes_in_use", 0),
                        "peak_bytes_in_use": m.get("peak_bytes_in_use", 0)}
                       for m in mem],
        }

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self.running.close()
