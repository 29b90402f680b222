"""`benchlib/wire.py`'s buffered reader over a socket pair, no server:
the framing survives any split of the byte stream, a reply delivered
whole costs one `recv`, and a peer that goes away is a ConnectionError."""

import socket
import struct
import threading

import pytest

from benchlib import wire


def _packet(seq: int, payload: bytes) -> bytes:
    return struct.pack("<I", len(payload))[:3] + bytes([seq & 0xFF]) + payload


def _lenenc(b: bytes) -> bytes:
    assert len(b) < 251
    return bytes([len(b)]) + b


def _coldef(name: str) -> bytes:
    return b"".join(_lenenc(x) for x in (b"def", b"", b"", b"", name.encode()))


_EOF = b"\xfe\x00\x00\x02\x00"
_GREETING = b"\x0a" + b"8.0.11-bench\0" + b"\0" * 40
_OK = b"\x00\x00\x00\x02\x00\x00\x00"


def _resultset(ncols: int, rows, tail: bytes = _EOF) -> bytes:
    """A text resultset as the server frames it, from sequence 1."""
    payloads = [bytes([ncols])]
    payloads += [_coldef(f"c{i}") for i in range(ncols)]
    payloads.append(_EOF)
    payloads += [b"".join(b"\xfb" if v is None else _lenenc(v.encode())
                          for v in row) for row in rows]
    payloads.append(tail)
    return b"".join(_packet(i + 1, p) for i, p in enumerate(payloads))


class _Sock:
    """The client's end of the pair: counts `recv` calls and caps the
    first ones at the sizes given, so a split falls where a test says."""

    def __init__(self, real):
        self.real, self.recvs, self.caps = real, 0, []

    def recv(self, n):
        self.recvs += 1
        return self.real.recv(min(n, self.caps.pop(0)) if self.caps else n)

    def setsockopt(self, *_a):       # TCP_NODELAY means nothing on a pair
        pass

    def __getattr__(self, name):
        return getattr(self.real, name)


@pytest.fixture()
def pair(monkeypatch):
    """-> (client, its counting socket, the peer's socket): a Client that
    has shaken hands with bytes written ahead into the pair."""
    ours, peer = socket.socketpair()
    sock = _Sock(ours)
    monkeypatch.setattr(wire.socket, "create_connection",
                        lambda *_a, **_k: sock)
    peer.sendall(_packet(0, _GREETING) + _packet(2, _OK))
    client = wire.Client("nowhere", 0, timeout_s=5.0)
    peer.settimeout(5.0)
    assert peer.recv(1 << 16)[4:8] == struct.pack(
        "<I", 0x200 | 0x8000 | 0x80000)       # the handshake response went
    sock.recvs = 0
    yield client, sock, peer
    ours.close()
    peer.close()


_ROWS = [("A", "F", "37734107.00", None), ("N", "O", "991417.00", "x" * 200)]
_COLS = ["c0", "c1", "c2", "c3"]


def test_one_reader_and_it_is_buffered():
    assert not hasattr(wire.Client, "_recv_exact")


def test_a_reply_split_at_every_byte_boundary(pair):
    client, sock, peer = pair
    reply = _resultset(4, _ROWS)
    for cut in range(1, len(reply)):
        peer.sendall(reply)
        sock.caps = [cut]
        assert client.query("select 1") == (_COLS, _ROWS), cut
        assert peer.recv(1 << 16) == _packet(0, b"\x03select 1")
        assert client._seq == 10          # 9 packets read, the next is 10


def test_a_reply_delivered_byte_by_byte(pair):
    client, sock, peer = pair
    reply = _resultset(4, _ROWS)
    peer.sendall(reply)
    sock.caps = [1] * len(reply)
    assert client.query("select 1") == (_COLS, _ROWS)
    assert sock.recvs == len(reply)


def test_a_17_packet_reply_delivered_whole_costs_two_recvs_at_most(pair):
    # Q1's answer: a column count, 10 definitions, EOF, 4 rows, EOF
    client, sock, peer = pair
    rows = [tuple(f"{r}.{c}" for c in range(10)) for r in range(4)]
    reply = _resultset(10, rows)
    packets, at = 0, 0
    while at < len(reply):
        at += 4 + int.from_bytes(reply[at:at + 3], "little")
        packets += 1
    assert packets == 17
    for _ in range(3):
        before = sock.recvs
        peer.sendall(reply)
        cols, got = client.query("select q1")
        assert (len(cols), got) == (10, rows)
        assert sock.recvs - before <= 2
    assert sock.recvs == 3                # one each, in fact


def test_several_replies_in_one_recv(pair):
    # two whole replies arrive together: the second waits in the buffer
    client, sock, peer = pair
    peer.sendall(_resultset(4, _ROWS) + _resultset(4, _ROWS[:1])
                 + _packet(1, _OK))
    assert client.query("a") == (_COLS, _ROWS)
    assert client.query("b") == (_COLS, _ROWS[:1])
    assert client.query("set c = 1") == 0       # an OK packet: affected rows
    assert sock.recvs == 1


def test_err_packet_mid_resultset(pair):
    client, sock, peer = pair
    err = b"\xff" + struct.pack("<H", 9008) + b"#HY000" + b"server is busy"
    peer.sendall(_resultset(4, _ROWS[:1], tail=err))
    with pytest.raises(wire.WireError) as e:
        client.query("select 1")
    assert e.value.code == 9008 and "server is busy" in str(e.value)
    peer.sendall(_resultset(4, _ROWS))    # the stream is still in step
    assert client.query("select 1") == (_COLS, _ROWS)


def test_err_packet_first(pair):
    client, _sock, peer = pair
    peer.sendall(_packet(1, b"\xff" + struct.pack("<H", 1064) + b"#42000"
                         + b"syntax"))
    with pytest.raises(wire.WireError) as e:
        client.query("selec")
    assert e.value.code == 1064


@pytest.mark.parametrize("tail", [0, 3])
def test_a_full_packet_and_its_continuation(pair, tail):
    # one row whose packet is exactly _MAX_PAYLOAD long is followed by a
    # continuation, empty when nothing is left over
    client, _sock, peer = pair
    n = wire._MAX_PAYLOAD + tail
    value = b"v" * (n - 4)                # 0xFD + a 3-byte length in front
    row = b"\xfd" + struct.pack("<I", len(value))[:3] + value
    head = _resultset(1, [])[:-len(_packet(0, _EOF))]     # up to the rows
    reply = (head + _packet(4, row[:wire._MAX_PAYLOAD])
             + _packet(5, row[wire._MAX_PAYLOAD:]) + _packet(6, _EOF))
    feeder = threading.Thread(target=peer.sendall, args=(reply,))
    feeder.start()
    cols, rows = client.query("select big")
    feeder.join()
    assert cols == ["c0"] and len(rows) == 1
    assert len(rows[0][0]) == n - 4 and set(rows[0][0]) == {"v"}
    assert client._seq == 7


@pytest.mark.parametrize("keep", [2, 4 + 1, 4 + 1 + 4 + 3])
def test_the_peer_closing_inside_a_header_or_a_payload(pair, keep):
    # 2: inside the first header; 5: after a whole packet, at a packet
    # edge; 12: inside the second packet's payload
    client, _sock, peer = pair
    peer.sendall(_resultset(4, _ROWS)[:keep])
    peer.close()
    with pytest.raises(ConnectionError):
        client.query("select 1")
