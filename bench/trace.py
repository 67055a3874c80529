"""Reduction of a profiler trace to device metrics.

A :class:`Trace` holds, per device, the intervals of the operations
(``XLA Ops`` line) and of the whole programs (``XLA Modules`` line) that
ran on it, and the host spans the harness wrote (``stage_call#<k>``),
all in nanoseconds on the trace's clock. Everything below is arithmetic
on those intervals:

* busy time: the union of operation intervals inside a window; idle
  share is 1 minus busy over the window;
* time per operation kind and per kernel, by name;
* device time of each stage call: the one program it ran, paired with
  it in order on its device (:func:`assign_programs`);
* idle gaps, each named by what the host was doing in its middle.

Operations are named by kind: the HLO instruction name without its
number (``fusion.132`` -> ``fusion``), and a Pallas kernel by the file
of the ``pallas_call`` that built it (``flash_attention``,
``decode_attention``, ``rmsnorm``), read from the trace's metadata
(:mod:`bench.xplane`). Control-flow operations that hold others
(``while``) are left out: only operations that hold none count.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from bench import xplane

Interval = Tuple[str, int, int]           # (name, start_ns, end_ns)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "stage_call#"


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Interval]]
    modules: Dict[int, List[Interval]]
    spans: Dict[int, Tuple[int, int]]     # call index -> (start, end)


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files under {logdir}")
    return paths[0]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    meta = xplane.event_metadata(path)
    ops: Dict[int, List[Interval]] = {}
    modules: Dict[int, List[Interval]] = {}
    spans: Dict[int, Tuple[int, int]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            names = meta.get(plane.name, {})
            kinds: Dict[str, str] = {}      # an operation's name -> kind

            def kind(name: str) -> str:
                if name not in kinds:
                    kinds[name] = op_name(name, names.get(name, {}))
                return kinds[name]

            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = leaves(
                        (kind(e.name), int(e.start_ns), int(e.end_ns))
                        for e in line.events)
                elif line.name == "XLA Modules":
                    modules[dev] = sorted(
                        ((e.name, int(e.start_ns), int(e.end_ns))
                         for e in line.events), key=lambda iv: iv[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans[int(e.name[len(SPAN_PREFIX):])] = (
                            int(e.start_ns), int(e.end_ns))
    return Trace(ops, modules, spans)


HLO_NAME = re.compile(r"^%([^ =]+?)(?:\.\d+)? = ")


def op_name(hlo: str, stats: Dict[str, object]) -> str:
    """Kind of one device operation, from its HLO text and metadata."""
    if "pallas_call" in str(stats.get("tf_op", "")):
        source = str(stats.get("source", "")).split(":")[0]
        return os.path.splitext(os.path.basename(source))[0] or "pallas_call"
    return op_kind(HLO_NAME.match(hlo).group(1) if HLO_NAME.match(hlo)
                   else hlo)


def op_kind(name: str) -> str:
    """``fusion.123`` -> ``fusion``: one name per kind of operation."""
    return re.sub(r"\.\d+$", "", name)


def leaves(intervals: Iterable[Interval]) -> List[Interval]:
    """The operations that hold no other, sorted by start (on one trace
    line, a control-flow operation's interval holds its body's)."""
    ivs = sorted(intervals, key=lambda iv: (iv[1], -iv[2]))
    return [iv for i, iv in enumerate(ivs)
            if not (i + 1 < len(ivs) and ivs[i + 1][1] < iv[2]
                    and ivs[i + 1][2] <= iv[2])]


def merge(intervals: Iterable[Interval]) -> List[Tuple[int, int]]:
    """Union of intervals as sorted, disjoint (start, end) pairs."""
    out: List[List[int]] = []
    for _, a, b in sorted(intervals, key=lambda iv: iv[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(merged: List[Tuple[int, int]], w0: int, w1: int) -> int:
    return sum(max(0, min(b, w1) - max(a, w0)) for a, b in merged)


def gaps(merged: List[Tuple[int, int]], w0: int, w1: int
         ) -> List[Tuple[int, int]]:
    """Idle stretches of [w0, w1) between busy intervals."""
    out, t = [], w0
    for a, b in merged:
        if b <= w0:
            continue
        if a >= w1:
            break
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < w1:
        out.append((t, w1))
    return out


def time_by_kind(intervals: Iterable[Interval], w0: int, w1: int
                 ) -> Dict[str, float]:
    """Seconds per operation kind, of operations starting in [w0, w1)."""
    out: Dict[str, float] = {}
    for name, a, b in intervals:
        if w0 <= a < w1:
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def assign_programs(trace: Trace, calls: Iterable[Tuple[int, int]]
                    ) -> Dict[int, Tuple[int, int]]:
    """The device program of each stage call, from ``(call index,
    device)`` pairs.

    A call runs one program, and one device runs its calls' programs in
    the order of the calls. Before its first traced call a device may
    hold programs of no traced call (a replica's warm-up, or a call
    already running when the trace began), and after its last one a
    call the trace stopped inside. So each device's traced calls, in the
    order their spans start, pair in order with its programs from the
    one that starts nearest the first call's span. The device's clock
    and the host's differ by up to a millisecond, so a program is never
    paired by lying inside a span: the next call's may start there.
    """
    by_dev: Dict[int, List[int]] = {}
    for k, dev in calls:
        if k in trace.spans:
            by_dev.setdefault(dev, []).append(k)
    out: Dict[int, Tuple[int, int]] = {}
    for dev, ks in by_dev.items():
        progs = trace.modules.get(dev, [])
        if not progs:
            continue
        ks.sort(key=lambda k: trace.spans[k][0])
        first = trace.spans[ks[0]][0]
        j = min(range(len(progs)), key=lambda i: abs(progs[i][1] - first))
        out.update({k: (s, e) for k, (_, s, e) in zip(ks, progs[j:])})
    return out


def clock_offset_ns(trace: Trace, host_starts: Dict[int, float]
                    ) -> Optional[float]:
    """Trace ns minus host seconds * 1e9, from the spans both sides saw."""
    diffs = [trace.spans[k][0] - host_starts[k] * 1e9
             for k in trace.spans if k in host_starts]
    return float(np.median(diffs)) if diffs else None
