"""Cut a capture down to its device planes, to keep a small recorded trace
beside ``trace.py``: ``python cut.py <in.xplane.pb> <out.xplane.pb>``.

An ``.xplane.pb`` is an ``XSpace``: field 1, repeated, is an ``XPlane``
whose field 2 is its name. Walking the top-level fields by hand needs no
protobuf schema. Nearly all of a capture is the host plane's Python events
(33 MB of 33.0 in this PR's first)."""

import sys


def varint(buf: bytes, at: int):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, at
        shift += 7


def plane_name(plane: bytes) -> str:
    at = 0
    while at < len(plane):
        tag, at = varint(plane, at)
        if tag & 7 == 2:
            size, at = varint(plane, at)
            if tag >> 3 == 2:
                return plane[at:at + size].decode()
            at += size
        elif tag & 7 == 0:
            _v, at = varint(plane, at)
        else:
            at += {1: 8, 5: 4}[tag & 7]
    return ""


def cut(data: bytes, prefix: str = "/device:TPU:") -> bytes:
    out, at = [], 0
    while at < len(data):
        start = at
        tag, at = varint(data, at)
        if tag & 7 != 2:
            raise ValueError("an XSpace holds length-delimited fields only")
        size, at = varint(data, at)
        body = data[at:at + size]
        at += size
        if tag >> 3 != 1 or plane_name(body).startswith(prefix):
            out.append(data[start:at])
    return b"".join(out)


if __name__ == "__main__":
    with open(sys.argv[1], "rb") as f:
        kept = cut(f.read())
    with open(sys.argv[2], "wb") as f:
        f.write(kept)
