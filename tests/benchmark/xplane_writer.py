"""Writes a small ``.xplane.pb`` by hand (protobuf wire format, the fields
of tsl's ``xplane.proto`` that a reader of names and times needs), so that
the trace reducer can be tested on a file whose every number is known."""

from __future__ import annotations


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, wire: int, payload: bytes) -> bytes:
    return _varint(num << 3 | wire) + payload


def _int(num: int, value: int) -> bytes:
    return _field(num, 0, _varint(value))


def _bytes(num: int, value: bytes) -> bytes:
    return _field(num, 2, _varint(len(value)) + value)


def xspace(planes: list[dict]) -> bytes:
    """``planes``: ``[{"name", "lines": [{"name", "timestamp_ns", "events":
    [(offset_ns, duration_ns, name)]}]}]``."""
    out = b""
    for pid, plane in enumerate(planes, 1):
        names: dict[str, int] = {}
        body = _int(1, pid) + _bytes(2, plane["name"].encode())
        for lid, line in enumerate(plane["lines"], 1):
            lb = _int(1, lid) + _bytes(2, line["name"].encode()) + _int(3, line.get("timestamp_ns", 0))
            for off_ns, dur_ns, name in line["events"]:
                mid = names.setdefault(name, len(names) + 1)
                lb += _bytes(4, _int(1, mid) + _int(2, off_ns * 1000) + _int(3, dur_ns * 1000))
            body += _bytes(3, lb)
        for name, mid in names.items():
            meta = _int(1, mid) + _bytes(2, name.encode())
            body += _bytes(4, _int(1, mid) + _bytes(2, meta))
        out += _bytes(1, body)
    return out
