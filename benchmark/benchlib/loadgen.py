"""The one general traffic generator. It reads a traffic mix's
parameters (a data file) and drives the server's wire port from threads
of this process: closed-loop streams that send their next statement when
the last one returned, and open-loop streams that send on a schedule
whatever the server does. A closed-loop spec with `"rounds": true`
sends in rounds: each of its streams sends its next statement when all
of them have their answers, and a round starts only while the window
lasts, so every stream completes the same count and no stream runs a
statement alone at the end.

Every seed gets the same work: an open loop's gaps are the quantiles of
its arrival distribution and its keys the quantiles of its key
distribution, each in an order drawn from the seed, and the rank -> key
map is a seed-dependent bijection. So two seeds differ in which rows are
hot and in where the bursts fall, never in how much is asked or in the
set of gaps it comes with.
"""

from __future__ import annotations

import contextlib
import math
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from benchlib.wire import Client, WireError

_DRAIN_S = 60.0       # an answer may come this long after the close


@dataclass
class Op:
    stream: str
    loop: str
    database: str
    statement: str
    key: int | None
    due: float            # seconds from the window's start
    sent: float = math.nan
    done: float = math.nan
    ok: bool = False
    rows: list | None = None
    error: str = ""


@dataclass
class Window:
    seconds: float
    t0: float = 0.0       # perf_counter at the window's start
    ops: list = field(default_factory=list)

    @property
    def wall_s(self) -> float:
        """Start to the last completion: all the time of all the work."""
        return max((o.done for o in self.ops if not math.isnan(o.done)),
                   default=0.0)


def stream_statements(spec: dict) -> list[str]:
    """The statement names one stream of a traffic mix sends."""
    return list(spec.get("statements", [])) + (
        [spec["statement"]] if "statement" in spec else [])


def open_schedule(spec: dict, seconds: float, n_keys: int, seed: int):
    """-> (due seconds, keys) of an open-loop stream: deterministic
    quantile samples, in an order drawn from the seed."""
    rate = float(spec["rate_per_s"])
    n = max(int(round(rate * seconds)), 1)
    u = (np.arange(n) + 0.5) / n
    arrivals = spec.get("arrivals", "poisson")
    if arrivals == "poisson":
        gaps = -np.log1p(-u)
    elif arrivals == "uniform":
        gaps = np.ones(n)
    else:
        raise ValueError(f"unknown arrivals {arrivals!r}")
    order = np.random.default_rng([seed, 1])
    gaps = order.permutation(gaps)
    due = np.cumsum(gaps) - gaps[0]
    due *= seconds * (n - 1) / n / max(due[-1], 1e-12) if n > 1 else 0.0
    keys = spec.get("keys", {"dist": "uniform"})
    if keys["dist"] == "zipf":
        w = 1.0 / np.arange(1, n_keys + 1) ** float(keys["theta"])
        cdf = np.cumsum(w)
        ranks = np.searchsorted(cdf, u * cdf[-1])
    elif keys["dist"] == "uniform":
        ranks = np.floor(u * n_keys).astype(np.int64)
    else:
        raise ValueError(f"unknown key distribution {keys['dist']!r}")
    ranks = np.minimum(order.permutation(ranks), n_keys - 1)
    mult = 2654435761 % n_keys or 1
    while math.gcd(mult, n_keys) != 1:
        mult += 1
    return due, (ranks * mult + seed % n_keys) % n_keys


class _Conn:
    """A connection that is reopened after a transport failure."""

    def __init__(self, port: int, database: str, timeout_s: float):
        self.args = ("127.0.0.1", port, database, timeout_s)
        self.client = Client(*self.args)

    def run(self, op: Op, sql: str, t0: float, annotate) -> None:
        op.sent = time.perf_counter() - t0
        try:
            with annotate(op.statement):
                res = self.client.query(sql)
            op.rows = res[1] if isinstance(res, tuple) else res
            op.ok = True
        except WireError as e:
            op.error = str(e)
        except (OSError, ConnectionError) as e:
            op.error = f"{type(e).__name__}: {e}"
            with contextlib.suppress(OSError):
                self.client.sock.close()
            with contextlib.suppress(OSError, ConnectionError):
                self.client = Client(*self.args)
        op.done = time.perf_counter() - t0

    def close(self) -> None:
        self.client.close()


class _Rounds:
    """The streams of one closed-loop spec that sends in rounds: each
    waits for the others before its next statement, and the last to
    arrive reads the window's clock once for all of them."""

    def __init__(self, streams: int, win: Window, timeout_s: float):
        self.win, self.timeout_s, self.go = win, timeout_s, False
        self.barrier = threading.Barrier(streams, action=self._decide)

    def _decide(self) -> None:
        self.go = time.perf_counter() - self.win.t0 < self.win.seconds

    def next(self) -> bool:
        """Whether this stream sends another statement."""
        self.barrier.wait(self.timeout_s)
        return self.go


def run_window(port: int, traffic: dict, statements: dict, counts: dict,
               seed: int, seconds: float, annotate=None) -> Window:
    """Drive every stream of `traffic` for `seconds`; statements then in
    flight finish. `counts[database][table]` sizes the key domains."""
    annotate = annotate or (lambda _name: contextlib.nullcontext())
    timeout_s = float(traffic.get("statement_timeout_s", 120.0))
    win = Window(seconds=seconds)
    lock = threading.Lock()
    threads, conns = [], []

    def closed_loop(name, conn, database, names, offset, rounds):
        k = 0
        while (rounds.next() if rounds else
               time.perf_counter() - win.t0 < seconds):
            stmt = names[(offset + k) % len(names)]
            k += 1
            op = Op(name, "closed", database, stmt, None,
                    time.perf_counter() - win.t0)
            conn.run(op, statements[stmt]["sql"], win.t0, annotate)
            with lock:
                win.ops.append(op)

    def open_loop(name, conn, database, stmt, due, keys, cursor):
        sql = statements[stmt]["sql"]
        while True:
            with lock:
                i = cursor[0]
                cursor[0] += 1
            if i >= len(due):
                return
            wait = win.t0 + due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            op = Op(name, "open", database, stmt, int(keys[i]),
                    float(due[i]))
            conn.run(op, sql.format(key=op.key), win.t0, annotate)
            with lock:
                win.ops.append(op)

    for si, spec in enumerate(traffic["streams"]):
        database = spec["database"]
        if spec["loop"] == "closed":
            count = int(spec.get("count", 1))
            rounds = (_Rounds(count, win, timeout_s + _DRAIN_S)
                      if spec.get("rounds") else None)
            for k in range(count):
                conn = _Conn(port, database, timeout_s)
                conns.append(conn)
                threads.append(threading.Thread(
                    target=closed_loop, daemon=True,
                    args=(f"s{si}.{k}", conn, database, spec["statements"],
                          k * int(spec.get("offset_step", 0)), rounds)))
        elif spec["loop"] == "open":
            stmt = spec["statement"]
            n_keys = counts[database][statements[stmt]["key_table"]]
            due, keys = open_schedule(spec, seconds, n_keys, seed + si)
            cursor = [0]
            for k in range(int(spec.get("connections", 8))):
                conn = _Conn(port, database, timeout_s)
                conns.append(conn)
                threads.append(threading.Thread(
                    target=open_loop, daemon=True,
                    args=(f"s{si}", conn, database, stmt, due, keys,
                          cursor)))
        else:
            raise ValueError(f"unknown loop kind {spec['loop']!r}")

    win.t0 = time.perf_counter()
    for t in threads:
        t.start()
    deadline = win.t0 + seconds + timeout_s + _DRAIN_S
    for t in threads:
        t.join(max(deadline - time.perf_counter(), 0.0))
    stuck = [t for t in threads if t.is_alive()]
    for c in conns:
        with contextlib.suppress(OSError, ConnectionError):
            c.close()
    if stuck:
        raise TimeoutError(f"{len(stuck)} stream(s) never returned")
    win.ops.sort(key=lambda o: (o.due, o.stream))
    return win
