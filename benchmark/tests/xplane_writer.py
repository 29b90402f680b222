"""A hand encoder for the profiler's XSpace protobuf, enough to write a
small .xplane.pb that `jax.profiler.ProfileData` reads back: planes,
lines, events with a name, a start and a duration. Field numbers are
those of tsl/profiler/protobuf/xplane.proto."""

from __future__ import annotations


def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _field(num: int, wire: int, payload: bytes) -> bytes:
    return _varint((num << 3) | wire) + payload


def _int(num: int, v: int) -> bytes:
    return _field(num, 0, _varint(v))


def _bytes(num: int, b: bytes) -> bytes:
    return _field(num, 2, _varint(len(b)) + b)


def encode(planes) -> bytes:
    """planes: [{"name", "lines": [{"name", "events": [(name, start_ns,
    end_ns)]}]}] -> the bytes of an XSpace."""
    space = b""
    for pid, plane in enumerate(planes, 1):
        meta: dict[str, int] = {}
        body = _int(1, pid) + _bytes(2, plane["name"].encode())
        for lid, line in enumerate(plane["lines"], 1):
            lb = _int(1, lid) + _bytes(2, line["name"].encode())
            lb += _int(3, 0)                        # timestamp_ns
            for name, start, end in line["events"]:
                mid = meta.setdefault(name, len(meta) + 1)
                ev = (_int(1, mid) + _int(2, int(start) * 1000)
                      + _int(3, int(end - start) * 1000))   # picoseconds
                lb += _bytes(4, ev)
            body += _bytes(3, lb)
        for name, mid in meta.items():
            em = _int(1, mid) + _bytes(2, name.encode())
            body += _bytes(4, _int(1, mid) + _bytes(2, em))  # map entry
        space += _bytes(1, body)
    return space
