"""tidb-tpu server process: `python -m tidb_tpu [flags]`.

Reference: /root/reference/tidb-server/main.go:127-152 — flag/config
merge, store open, bootstrap, MySQL wire server + HTTP status server,
signal-driven graceful close. Config precedence: built-in defaults <
TIDB_TPU_* environment < --config TOML file < explicit CLI flags.
"""

from __future__ import annotations

import argparse
import logging
import signal
import sys
import threading


def _apply_config_file(path: str) -> dict:
    """TOML config tree (ref: config/config.go:29). Returns the flat
    {sysvar_name: value} dict of the [variables] table plus top-level
    server keys."""
    import tomllib
    with open(path, "rb") as f:
        return tomllib.load(f)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tidb_tpu", description="TPU-native HTAP SQL server")
    # None defaults distinguish "flag given" from "use config/default":
    # precedence is defaults < env < config file < explicit flags
    p.add_argument("--host", default=None)
    p.add_argument("-P", "--port", type=int, default=None)
    p.add_argument("--status-port", type=int, default=None)
    p.add_argument("--no-status", action="store_true",
                   help="disable the HTTP status server")
    p.add_argument("--config", help="TOML config file")
    p.add_argument("--mesh", type=int, default=None, metavar="N",
                   help="enable an N-device mesh (default: all devices)")
    p.add_argument("--no-mesh", action="store_true")
    p.add_argument("--token-limit", type=int, default=1000,
                   help="max concurrent connections (ref: TokenLimit)")
    p.add_argument("--log-level", default="info")
    p.add_argument("--slow-threshold-ms", type=int, default=None)
    p.add_argument("--set", action="append", default=[], metavar="VAR=V",
                   help="set a tidb_tpu_* sysvar (repeatable)")
    p.add_argument("--store", default=None, metavar="HOST:PORT",
                   help="connect to a store-plane server (fleet mode: "
                        "this process is a stateless SQL server with "
                        "its own coherent caches) instead of hosting an "
                        "in-process store")
    return p


class Running:
    """What `start()` brought up; `close()` tears it down in reverse."""

    def __init__(self, storage, server, status):
        self.storage = storage
        self.server = server
        self.status = status

    def close(self) -> None:
        if self.status is not None:
            from tidb_tpu import member
            member.stop_heartbeat()
            self.status.close()
        self.server.close()
        self.storage.close()


def start(*, host: str = "127.0.0.1", port: int = 4000,
          status_port: int | None = 10080, mesh: int | None = None,
          no_mesh: bool = False, token_limit: int = 1000,
          store: str | None = None) -> Running:
    """Bring up the device plane, storage, the MySQL wire server and
    (unless status_port is None) the HTTP status server — THE start-up
    path: `python -m tidb_tpu` and chip_smoke.py both call it, so the
    smoke cannot drift from the entry point. Port 0 binds an ephemeral
    port (read it back from `.server.port` / `.status.port`).

    A process that cannot see its devices does not start: no backend,
    or fewer devices than `mesh`, raises. Host-only serving is only
    what was asked for — `no_mesh` together with tidb_tpu_device=0 —
    and never a consequence of a failed device bring-up."""
    log = logging.getLogger("tidb_tpu.server")
    from tidb_tpu import config
    from tidb_tpu.util import compile_cache
    cc = compile_cache.stats()
    log.info("XLA compile cache: %s (%s entries)", cc["dir"],
             cc["entries"])
    # the kernel profiling plane rides every dispatch; say up front
    # whether it is armed and how much history it may keep
    from tidb_tpu import profiler
    ks = profiler.stats()
    log.info("kernel profiler: %s (cap %d profiles, compile-cache "
             "hits=%d misses=%d)",
             "on" if ks["enabled"] else "off", ks["cap"],
             cc["hits"], cc["misses"])
    log.info("serving: scheduler inflight=%d (bytes gate %d), "
             "server mem quota=%d (admission %s, timeout %dms)",
             config.sched_inflight(), config.sched_inflight_bytes(),
             config.server_mem_quota(),
             "on" if config.server_mem_quota() else "off",
             config.admission_timeout_ms())

    from tidb_tpu import devplane
    if no_mesh and not config.device_enabled():
        log.info("devices: none requested (--no-mesh, tidb_tpu_device=0)"
                 "; host execution only")
    else:
        import jax
        devs = jax.devices()
        log.info("devices: platform=%s device_kind=%s count=%d",
                 devs[0].platform, devs[0].device_kind, len(devs))
    if no_mesh:
        devplane.disable_mesh()
    else:
        devplane.enable_mesh(mesh)
        log.info("device mesh: %s", devplane.active_mesh().devices.shape)

    from tidb_tpu.server import Server
    from tidb_tpu.server.status import StatusServer

    if store:
        from tidb_tpu.store.remote import connect
        h, _, pt = store.rpartition(":")
        storage = connect(h or "127.0.0.1", int(pt), local_cache=True)
        log.info("fleet mode: store plane at %s", store)
    else:
        from tidb_tpu.store.storage import new_mock_storage
        storage = new_mock_storage()
    server = Server(storage, host=host, port=port,
                    token_limit=token_limit)
    server.start()
    log.info("MySQL protocol on %s:%d", host, server.port)
    status = None
    if status_port is not None:
        status = StatusServer(storage, server, host=host,
                              port=status_port)
        status.start()
        log.info("status API on %s:%d", host, status.port)
        # fleet membership (tidb_tpu/member.py): identity = the status
        # port peers fan cluster_* queries out to, so registration is
        # tied to the status server being up. The heartbeat publishes
        # through whichever storage this process uses — the shared
        # store plane in fleet mode, the in-process store standalone
        # (where this member is then the whole visible fleet).
        from tidb_tpu import member
        member.set_identity(host, status.port, "sql")
        member.start_heartbeat(storage)
    return Running(storage, server, status)


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "storeserve":
        # store-plane server: one MVCCStore + TSO + region map behind
        # the wire protocol, shared by N stateless SQL servers
        from tidb_tpu.store.remote import serve_main
        return serve_main(argv[1:])
    args = build_parser().parse_args(argv)
    logging.basicConfig(
        level=getattr(logging, args.log_level.upper(), logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s %(message)s")
    log = logging.getLogger("tidb_tpu.server")

    from tidb_tpu import config
    if args.config:
        tree = _apply_config_file(args.config)
        for k, v in (tree.get("variables") or {}).items():
            config.set_var(k, v)
        # explicit CLI flags beat the file (main.go:257 overrideConfig)
        if args.host is None:
            args.host = tree.get("host")
        if args.port is None and "port" in tree:
            args.port = int(tree["port"])
        if args.status_port is None and "status_port" in tree:
            args.status_port = int(tree["status_port"])
    args.host = args.host or "127.0.0.1"
    args.port = 4000 if args.port is None else args.port
    args.status_port = 10080 if args.status_port is None \
        else args.status_port
    if args.slow_threshold_ms is not None:
        config.set_var("tidb_tpu_slow_query_ms", args.slow_threshold_ms)
    for kv in args.set:
        name, _, val = kv.partition("=")
        config.set_var(name, val)

    running = start(host=args.host, port=args.port,
                    status_port=None if args.no_status
                    else args.status_port,
                    mesh=args.mesh, no_mesh=args.no_mesh,
                    token_limit=args.token_limit, store=args.store)

    stop = threading.Event()

    def _on_signal(_sig, _frm):
        stop.set()

    signal.signal(signal.SIGINT, _on_signal)
    signal.signal(signal.SIGTERM, _on_signal)
    stop.wait()
    log.info("shutting down")
    running.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
