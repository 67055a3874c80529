"""Read the per-operation metadata of an ``.xplane.pb`` trace.

``jax.profiler.ProfileData`` gives every event's name (for a device
operation, its HLO text) and time, but not the metadata stats that say
where an operation came from. Pallas kernels show up there only as
``custom-call`` instructions with generated names (``closed_call.63``);
what identifies one is the ``source`` stat, the line of the
``pallas_call`` that built it (``.../kernels/flash_attention.py:111``).

This is a minimal reader of the protobuf wire format for just that:
``XSpace.planes[].{name, event_metadata, stat_metadata}``. Field
numbers follow ``tsl/profiler/protobuf/xplane.proto``.
"""

from __future__ import annotations

import struct
from typing import Dict, Iterator, Tuple

VARINT, I64, LEN, I32 = 0, 1, 2, 5


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; LEN values are
    bytes, the others ints."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == VARINT:
            v, i = _varint(buf, i)
        elif wt == I64:
            v, i = struct.unpack_from("<q", buf, i)[0], i + 8
        elif wt == I32:
            v, i = struct.unpack_from("<i", buf, i)[0], i + 4
        elif wt == LEN:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield num, wt, v


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, val = 0, b""
    for num, _, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _stat(buf: bytes, names: Dict[int, str]) -> Tuple[int, object]:
    mid, val = 0, None
    for num, wt, v in _fields(buf):
        if num == 1:
            mid = v
        elif num == 5:                      # str_value
            val = v.decode("utf-8", "replace")
        elif num == 7:                      # ref_value: a stat name
            val = names.get(v, "")
        elif num in (3, 4):                 # uint64 / int64
            val = v
    return mid, val


def event_metadata(path: str, plane_prefix: str = "/device:"
                   ) -> Dict[str, Dict[str, Dict[str, object]]]:
    """``{plane name: {event name: {stat name: value}}}`` for the planes
    whose name starts with `plane_prefix`."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, Dict[str, object]]] = {}
    for num, _, plane in _fields(space):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pnum, _, v in _fields(plane):
            if pnum == 2:
                name = v.decode("utf-8", "replace")
            elif pnum == 4:
                metas.append(_map_entry(v)[1])
            elif pnum == 5:
                sid, smeta = _map_entry(v)
                for snum, _, sv in _fields(smeta):
                    if snum == 2:
                        stat_names[sid] = sv.decode("utf-8", "replace")
        if not name.startswith(plane_prefix):
            continue
        events: Dict[str, Dict[str, object]] = {}
        for meta in metas:
            ename, stats = "", {}
            for mnum, _, mv in _fields(meta):
                if mnum == 2:
                    ename = mv.decode("utf-8", "replace")
                elif mnum == 5:
                    sid, val = _stat(mv, stat_names)
                    stats[stat_names.get(sid, str(sid))] = val
            events[ename] = stats
        out[name] = events
    return out
