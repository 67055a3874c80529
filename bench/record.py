"""What one run recorded, and the arithmetic the metric readers share.

A :class:`Run` is handed to every per-layer metric reader
(``bench/metrics/<name>.py``). Times on the host are seconds on the
executor's clock (zero when the trace starts being served); times in
the trace are nanoseconds on the trace's clock, and ``to_ns`` maps the
first onto the second through the stage-call spans both sides saw.
"""

from __future__ import annotations

import bisect
import dataclasses
from types import ModuleType
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench import flops, trace as tr


@dataclasses.dataclass
class Call:
    index: int
    device: int
    t0: float
    t1: float
    rows: int


@dataclasses.dataclass
class Run:
    config: Dict[str, Any]
    family: Optional[ModuleType]    # bench/families/<config["family"]>.py
    peak: Dict[str, Any]
    prompt: int
    gen: int
    buckets: Tuple[int, ...]
    arrival: np.ndarray       # nominal arrival of every request
    started: np.ndarray       # start of the batch that served it (nan: never)
    done: np.ndarray          # answer time (inf: never)
    w0: float
    w1: float
    calls: List[Call]
    trace: Optional[tr.Trace] = None
    offset_ns: Optional[float] = None
    _op_starts: Dict[int, List[int]] = dataclasses.field(
        default_factory=dict, repr=False)
    _programs: Optional[Dict[int, Tuple[int, int]]] = dataclasses.field(
        default=None, repr=False)

    # -- host side -----------------------------------------------------------
    def due(self) -> np.ndarray:
        return (self.arrival >= self.w0) & (self.arrival < self.w1)

    def window_calls(self) -> List[Call]:
        return [c for c in self.calls if self.w0 <= c.t0 < self.w1]

    def bucket(self, rows: int) -> int:
        return next(b for b in self.buckets if b >= rows)

    # -- trace side ----------------------------------------------------------
    def traced(self) -> bool:
        return self.trace is not None and self.offset_ns is not None

    def to_ns(self, t: float) -> int:
        return int(round(t * 1e9 + self.offset_ns))

    def window_ns(self) -> Tuple[int, int]:
        return self.to_ns(self.w0), self.to_ns(self.w1)

    def devices(self) -> List[int]:
        return sorted(self.trace.ops)

    def call_programs(self, call: Call) -> List[Tuple[int, int]]:
        """(start, end) of the device program a call ran, if traced."""
        if self._programs is None:
            self._programs = tr.assign_programs(
                self.trace, ((c.index, c.device) for c in self.calls))
        prog = self._programs.get(call.index)
        return [prog] if prog else []

    def traced_window_calls(self) -> List[Tuple[Call, float, float]]:
        """(call, host span s, device s) of every window call the trace
        holds whole."""
        out = []
        for c in self.window_calls():
            span = self.trace.spans.get(c.index)
            progs = self.call_programs(c)
            if span is None or not progs:
                continue
            out.append((c, (span[1] - span[0]) * 1e-9,
                        sum(e - s for s, e in progs) * 1e-9))
        return out

    def _starts(self, device: int) -> List[int]:
        if device not in self._op_starts:
            self._op_starts[device] = [
                s for _, s, _ in self.trace.ops.get(device, [])]
        return self._op_starts[device]

    def kernel_s(self, call: Call, kernel: str) -> float:
        """Seconds of `kernel`'s operations inside a call's programs."""
        ops = self.trace.ops.get(call.device, [])
        starts = self._starts(call.device)
        total = 0
        for a, b in self.call_programs(call):
            i = bisect.bisect_left(starts, a)
            while i < len(ops) and ops[i][1] <= b:
                name, s, e = ops[i]
                if name == kernel:
                    total += e - s
                i += 1
        return total * 1e-9


def mean_or_none(values: List[float]) -> Optional[float]:
    return float(np.mean(values)) if values else None


def host_ms(run: Run) -> Optional[float]:
    """Mean host time of a stage call outside its device program."""
    if not run.traced():
        return None
    return mean_or_none([(span - dev) * 1e3
                         for _, span, dev in run.traced_window_calls()])


def step_ms(run: Run) -> Optional[float]:
    """Mean device time of the generate program per stage call."""
    if not run.traced():
        return None
    return mean_or_none([dev * 1e3 for _, _, dev in run.traced_window_calls()])


def kernel_roofline_pct(run: Run, kernel: str) -> Optional[float]:
    """The least time the chip could take for `kernel`'s calls in the
    window's stage calls (the family's count of each call), over the
    time they took."""
    if not run.traced():
        return None
    ideal = took = 0.0
    for c, _, _ in run.traced_window_calls():
        t = run.kernel_s(c, kernel)
        if t <= 0.0:
            continue
        took += t
        for name, f, nbytes in run.family.kernel_calls(
                run.config, run.bucket(c.rows), run.prompt, run.gen):
            if name == kernel:
                ideal += flops.roofline_s(f, nbytes, run.peak)
    return 100.0 * ideal / took if took > 0.0 else None
