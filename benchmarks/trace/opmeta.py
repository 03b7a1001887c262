"""What an ``.xplane.pb`` knows about each operation, beyond what
``jax.profiler.ProfileData`` shows.

``ProfileData`` gives every *event's* stats (``device_offset_ps``,
``device_duration_ps``). The file also holds, per plane, a table of *event
metadata* whose entries carry stats of their own: for a TPU's device plane
the XLA operation's ``hlo_category``, ``flops``, ``bytes_accessed``, the JAX
``tf_op`` path (``jit(round_step)/fedml.step/while/body/...``: the program's
``jax.named_scope`` names arrive here) and ``source`` (file:line). An
entry's ``name`` is the HLO instruction text that also names the event, so
the join with ``ProfileData`` is by name.

A wire reader, pure Python, standard library only (the ``tsl`` / ``xprof``
protos are not importable here). Field numbers, from
``tsl/profiler/protobuf/xplane.proto``:

    XSpace          1 planes (XPlane, repeated)
    XPlane          1 id   2 name   3 lines   4 event_metadata (map<int64, XEventMetadata>)
                    5 stat_metadata (map<int64, XStatMetadata>)   6 stats
    map entry       1 key   2 value
    XEventMetadata  1 id   2 name   3 metadata (bytes)   4 display_name   5 stats (XStat, repeated)
    XStatMetadata   1 id   2 name   3 description
    XStat           1 metadata_id   2 double_value (fixed64)   3 uint64_value   4 int64_value
                    5 str_value   6 bytes_value   7 ref_value (id of an XStatMetadata whose name is the value)
"""

from __future__ import annotations

import struct

#: the stats of an event-metadata entry that ``read`` keeps
KEYS = ("tf_op", "hlo_category", "flops", "bytes_accessed", "source")


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """Yield ``(field number, wire type, value)`` of one message: an int for
    varints and fixed-width fields, a ``memoryview`` for length-delimited."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        num, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = struct.unpack_from("<Q", buf, i)[0], i + 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 5:
            v, i = struct.unpack_from("<I", buf, i)[0], i + 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}: not an xplane file?")
        yield num, wt, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names: dict):
    """One XStat -> (name, value); a ``ref_value`` is resolved to the name
    of the stat-metadata entry it points to."""
    key, value = None, None
    for num, wt, v in fields(buf):
        if num == 1:
            key = stat_names.get(v, str(v))
        elif num == 2:
            value = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif num in (3, 4):
            value = v - (1 << 64) if num == 4 and v >= 1 << 63 else v
        elif num in (5, 6):
            value = _text(v)
        elif num == 7:
            value = stat_names.get(v, str(v))
    return key, value


def _map_value(buf):
    for num, wt, v in fields(buf):
        if num == 2 and wt == 2:
            return v
    return None


def entries(path: str):
    """Yield ``(plane name, event name, {stat name: value})`` for every
    event-metadata entry of every plane, in file order. Two entries may share
    a name (an async ``copy-start`` has one for each line it appears on)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for num, wt, plane in fields(space):
        if num != 1 or wt != 2:
            continue
        name, metas, stat_names = "", [], {}
        for pnum, pwt, v in fields(plane):
            if pnum == 2:
                name = _text(v)
            elif pnum == 4:
                metas.append(_map_value(v))
            elif pnum == 5:
                sid = sname = None
                for snum, _swt, sv in fields(_map_value(v)):
                    if snum == 1:
                        sid = sv
                    elif snum == 2:
                        sname = _text(sv)
                stat_names[sid] = sname
        for meta in metas:
            ev_name, stats = "", {}
            for mnum, mwt, v in fields(meta):
                if mnum == 2:
                    ev_name = _text(v)
                elif mnum == 5 and mwt == 2:
                    k, val = _stat(v, stat_names)
                    stats[k] = val
            yield name, ev_name, stats


def read(path: str) -> dict:
    """-> ``{device plane: {event name: {"tf_op", "hlo_category", "flops",
    "bytes_accessed", "source"}}}`` for the ``/device:`` planes; a key an
    entry lacks is left out of its dict."""
    out: dict = {}
    for plane, ev, st in entries(path):
        if plane.startswith("/device:"):
            out.setdefault(plane, {})[ev] = {k: st[k] for k in KEYS if k in st}
    return out


if __name__ == "__main__":
    import sys

    for plane, table in read(sys.argv[1]).items():
        print(f"PLANE {plane}: {len(table)} event-metadata entries")
        for ev, st in table.items():
            print(f"  {ev[:60]!r}: {st}")
