"""MySQL wire protocol server tests.

Ref model: server/conn_test.go + driving the stack through the real
protocol the way a MySQL client would (testkit goes through Session;
these go through the socket).
"""

import pytest

from tests.mysql_client import MiniClient, MySQLError
from tidb_tpu.server import Server
from tidb_tpu.store import new_mock_storage


@pytest.fixture
def srv():
    storage = new_mock_storage()
    storage.async_commit_secondaries = False
    server = Server(storage, port=0)
    server.start()
    yield server
    server.close()
    storage.close()


@pytest.fixture
def cli(srv):
    c = MiniClient("127.0.0.1", srv.port)
    c.query("CREATE DATABASE IF NOT EXISTS test")
    c.use("test")
    yield c
    c.close()


class TestProtocol:
    def test_handshake_ping(self, srv):
        c = MiniClient("127.0.0.1", srv.port)
        c.ping()
        c.close()

    def test_query_roundtrip(self, cli):
        assert cli.query(
            "CREATE TABLE t (id BIGINT PRIMARY KEY, v INT, s VARCHAR(10))"
        ) == 0
        assert cli.query(
            "INSERT INTO t VALUES (1, 10, 'a'), (2, 20, 'b'), (3, NULL, NULL)"
        ) == 3
        cols, rows = cli.query("SELECT * FROM t ORDER BY id")
        assert cols == ["id", "v", "s"]
        assert rows == [("1", "10", "a"), ("2", "20", "b"),
                        ("3", None, None)]

    def test_expressions_and_aggregates(self, cli):
        cli.query("CREATE TABLE a (x BIGINT PRIMARY KEY, y DOUBLE)")
        cli.query("INSERT INTO a VALUES (1, 1.5), (2, 2.5), (3, 4.0)")
        _cols, rows = cli.query(
            "SELECT COUNT(*), SUM(y), MIN(x) FROM a WHERE y > 1")
        assert rows == [("3", "8.0", "1")]

    def test_error_packet(self, cli):
        with pytest.raises(MySQLError):
            cli.query("SELECT * FROM missing_table")
        # connection still usable after an error
        assert cli.query("CREATE TABLE ok (a BIGINT PRIMARY KEY)") == 0

    def test_init_db_and_connect_with_db(self, srv):
        c1 = MiniClient("127.0.0.1", srv.port)
        c1.query("CREATE DATABASE IF NOT EXISTS d2")
        c1.close()
        c2 = MiniClient("127.0.0.1", srv.port, db="d2")
        c2.query("CREATE TABLE t (a BIGINT PRIMARY KEY)")
        c2.query("INSERT INTO t VALUES (9)")
        _cols, rows = c2.query("SELECT a FROM t")
        assert rows == [("9",)]
        c2.close()

    def test_unknown_db_errors(self, srv):
        c = MiniClient("127.0.0.1", srv.port)
        with pytest.raises(MySQLError):
            c.use("no_such_db")
        c.close()

    def test_chaos_surfaced_errors_carry_retryable_codes(self, cli):
        """Device-plane faults that exhaust the in-process recovery
        chain must reach the wire as RETRYABLE codes — the contract the
        chaos harness (docs/ROBUSTNESS.md) holds clients to. The armed
        DispatchTimeoutError flavor skips the retry/degrade chain, so
        exactly one statement fails with ER_DEVICE_FAULT (9009)."""
        from tidb_tpu import config, errcode, sched
        from tidb_tpu.util import failpoint
        cli.query("CREATE TABLE ft (a BIGINT PRIMARY KEY, v BIGINT)")
        cli.query("INSERT INTO ft VALUES " +
                  ",".join(f"({i},{i % 9})" for i in range(64)))
        old = config.get_var("tidb_tpu_device_min_rows")
        config.set_var("tidb_tpu_device_min_rows", 1)
        failpoint.enable(
            "device/dispatch",
            "1*raise(DispatchTimeoutError:device fault: injected)")
        try:
            with pytest.raises(MySQLError) as ei:
                cli.query("SELECT v, COUNT(*) FROM ft GROUP BY v")
        finally:
            failpoint.disable("device/dispatch")
            config.set_var("tidb_tpu_device_min_rows", old)
            sched.device_health().note_ok()
        assert ei.value.code == errcode.ER_DEVICE_FAULT == 9009
        assert errcode.is_retryable(ei.value.code)
        # the retryable contract means a verbatim replay succeeds
        _cols, rows = cli.query(
            "SELECT v, COUNT(*) FROM ft GROUP BY v ORDER BY v")
        assert len(rows) == 9


class TestConcurrency:
    def test_two_connections_txn_isolation(self, srv):
        c1 = MiniClient("127.0.0.1", srv.port)
        c1.query("CREATE DATABASE IF NOT EXISTS test")
        c1.use("test")
        c1.query("CREATE TABLE t (a BIGINT PRIMARY KEY, b INT)")
        c1.query("INSERT INTO t VALUES (1, 1)")
        c2 = MiniClient("127.0.0.1", srv.port)
        c2.use("test")
        # c1 opens a txn and writes; c2 must not see it until commit
        c1.query("BEGIN")
        c1.query("UPDATE t SET b = 99 WHERE a = 1")
        _c, rows = c2.query("SELECT b FROM t WHERE a = 1")
        assert rows == [("1",)]
        c1.query("COMMIT")
        _c, rows = c2.query("SELECT b FROM t WHERE a = 1")
        assert rows == [("99",)]
        c1.close()
        c2.close()

    def test_many_parallel_clients(self, srv):
        import threading
        boot = MiniClient("127.0.0.1", srv.port)
        boot.query("CREATE DATABASE IF NOT EXISTS test")
        boot.use("test")
        boot.query("CREATE TABLE p (a BIGINT PRIMARY KEY, b INT)")
        boot.close()
        errs = []

        def worker(i):
            try:
                c = MiniClient("127.0.0.1", srv.port, db="test")
                c.query(f"INSERT INTO p VALUES ({i}, {i * 10})")
                _cols, rows = c.query(f"SELECT b FROM p WHERE a = {i}")
                assert rows == [(str(i * 10),)]
                c.close()
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errs == []
        check = MiniClient("127.0.0.1", srv.port, db="test")
        _cols, rows = check.query("SELECT COUNT(*) FROM p")
        assert rows == [("8",)]
        check.close()


class _RecordingSock:
    """A socket that keeps each `sendall` apart."""

    def __init__(self):
        self.sends = []

    def sendall(self, b):
        self.sends.append(bytes(b))


def _framed(payloads):
    """What the per-packet writer put on the wire: header + payload each,
    sequence numbers from 0."""
    return b"".join(
        len(p).to_bytes(3, "little") + bytes([i & 0xFF]) + p
        for i, p in enumerate(payloads))


class TestOneWriteAResponse:
    """The packet writer buffers a command's packets and flushes before
    it next reads: many small writes met Nagle's algorithm and the
    client's delayed ACK, 40 ms a statement."""

    def test_q1_shaped_resultset_leaves_in_one_sendall(self):
        from tidb_tpu import metrics
        from tidb_tpu.server import ClientConn
        from tidb_tpu.server.packet import lenenc_int
        from tidb_tpu.session import ResultSet

        sock = _RecordingSock()
        conn = ClientConn(server=None, sock=sock, conn_id=1)
        cols = [f"c{i}" for i in range(10)]
        rows = [tuple(f"{r}.{c}" for c in range(10)) for r in range(4)]
        calls = metrics.snapshot().get(metrics.WIRE_WRITE_CALLS, 0)
        conn._write_resultset(ResultSet(cols, rows))
        assert len(sock.sends) == 1
        assert metrics.snapshot()[metrics.WIRE_WRITE_CALLS] - calls == 1
        eof = b"\xfe\x00\x00\x02\x00"
        packets = [lenenc_int(10)]
        packets += [ClientConn._column_def(c, None) for c in cols]
        packets += [eof] + [ClientConn._encode_row(r) for r in rows] + [eof]
        assert len(packets) == 17
        assert sock.sends[0] == _framed(packets)

    def test_write_packet_alone_writes_nothing(self):
        from tidb_tpu.server.packet import PacketIO
        sock = _RecordingSock()
        pkt = PacketIO(sock)
        pkt.write_packet(b"abc")
        pkt.write_packet(b"")
        assert sock.sends == [] and pkt.sent == 11 and pkt.seq == 2
        pkt.flush()
        pkt.flush()                               # nothing left: no write
        assert sock.sends == [_framed([b"abc", b""])]

    def test_read_flushes_first(self):
        import socket

        from tidb_tpu.server.packet import PacketIO
        a, b = socket.socketpair()
        try:
            b.settimeout(10)
            server = PacketIO(a)
            server.write_packet(b"\x00reply")
            b.sendall(_framed([b"\x0e"]))
            # the read hands over what the writer held: the server never
            # blocks on a peer that is waiting for bytes in its buffer
            assert server.read_packet() == b"\x0e"
            assert b.recv(64) == _framed([b"\x00reply"])
        finally:
            a.close()
            b.close()

    def test_large_resultset_streams_in_bounded_pieces(self, cli,
                                                       monkeypatch):
        from tidb_tpu.server import packet
        n, width = 6000, 96            # ~ 600 KiB: nine times the bound
        cli.query("CREATE TABLE big (a BIGINT PRIMARY KEY, s VARCHAR(120))")
        for lo in range(0, n, 1000):
            cli.query("INSERT INTO big VALUES " + ",".join(
                f"({i}, '{str(i).rjust(width, 'x')}')"
                for i in range(lo, lo + 1000)))
        held = []                      # the buffer's size at each flush
        flush = packet.PacketIO.flush

        def watched(self):
            held.append(len(self._out))
            flush(self)

        monkeypatch.setattr(packet.PacketIO, "flush", watched)
        _cols, rows = cli.query("SELECT a, s FROM big ORDER BY a")
        assert rows == [(str(i), str(i).rjust(width, "x"))
                        for i in range(n)]
        assert n * width > 8 * packet.FLUSH_BYTES
        assert max(held) <= packet.FLUSH_BYTES
        assert sum(1 for h in held if h) >= 9

    def test_failed_login_err_arrives_before_the_close(self, srv):
        with pytest.raises(MySQLError) as e:
            MiniClient("127.0.0.1", srv.port, user="nobody",
                       password="wrong")
        assert e.value.code == 1045 and "Access denied" in str(e.value)

    def test_accepted_socket_has_nodelay(self, srv, cli):
        import socket
        with srv._mu:
            socks = [c.sock for c in srv._conns]
        assert socks
        for s in socks:
            assert s.getsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY) != 0

    def test_back_to_back_selects_do_not_wait_for_a_delayed_ack(self, cli):
        import statistics
        import time
        cli.query("CREATE TABLE q (a BIGINT PRIMARY KEY, b INT, c INT)")
        cli.query("INSERT INTO q VALUES " + ",".join(
            f"({i}, {i * 2}, {i * 3})" for i in range(4)))
        took = []
        for _ in range(20):
            t0 = time.perf_counter()
            _cols, rows = cli.query("SELECT a, b, c FROM q ORDER BY a")
            took.append(time.perf_counter() - t0)
            assert len(rows) == 4
        # 3 column definitions + 4 rows + the rest: ten packets a reply.
        # Sent one by one, every reply after the first waits 40 ms
        assert statistics.median(took) < 0.025, took
