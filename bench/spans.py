"""The program's own spans and phase scopes in a profiler trace, and the
per-layer quantities they give.

The serving path names its steps in the trace
(``repro.serving.spans``): on the host, ``executor.inject``,
``executor.form``, ``executor.batch``, ``executor.complete`` and
``runtime.pad|put|launch|fetch``, with their args; on the device, the
generate program's ``prefill`` and ``decode`` scopes in each
operation's ``tf_op``. :func:`load` reads them from an ``.xplane.pb``
beside the harness's ``stage_call#<k>`` spans, keeping each host span's
thread. A trace of a program without them gives a :class:`Program`
with nothing in it, and every quantity below reads ``None``.

The quantities take the :class:`bench.record.Run` of the same trace
(window, calls, device program of each call):

* :func:`phase_ms` — device time of one scope's operations per call;
* :func:`runtime_prep_ms` — ``runtime.pad`` + ``put`` + ``launch`` per
  call; :func:`runtime_fetch_ms` — from the end of the call's device
  program to the end of ``runtime.fetch``;
* :func:`executor_host_ms` — ``executor.complete`` plus the next
  ``executor.form`` on the replica's thread, per call;
* :func:`inject_lag_ms` — p99 of ``lag_us`` of the window's requests;
* :func:`qualify` — an idle gap's name, extended by the innermost
  program span open at its middle on the device's replica thread.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench import trace as tr, xplane

PREFIXES = ("executor.", "runtime.", tr.SPAN_PREFIX)
SCOPES = ("prefill", "decode")


@dataclasses.dataclass
class Span:
    name: str
    start: int          # ns on the trace's clock
    end: int
    thread: int         # one number per host trace line
    args: Dict[str, Any]


@dataclasses.dataclass
class Program:
    spans: List[Span]                       # sorted by start
    scoped: Dict[int, List[tr.Interval]]    # device -> (scope, start, end)

    def __post_init__(self):
        self._by_name: Dict[str, List[Span]] = {}
        self._by_thread: Dict[int, List[Span]] = {}
        for s in self.spans:
            self._by_name.setdefault(s.name, []).append(s)
            self._by_thread.setdefault(s.thread, []).append(s)
        self._thread_starts = {th: [s.start for s in ss]
                               for th, ss in self._by_thread.items()}
        self._starts = {d: [s for _, s, _ in ops]
                        for d, ops in self.scoped.items()}

    def named(self, name: str) -> List[Span]:
        return self._by_name.get(name, [])

    def has_spans(self) -> bool:
        return any(s.name.startswith(PREFIXES[:2]) for s in self.spans)

    def stage_call(self, index: int) -> Optional[Span]:
        found = self.named(f"{tr.SPAN_PREFIX}{index}")
        return found[0] if found else None

    def inside(self, outer: Span) -> List[Span]:
        """Spans on `outer`'s thread that lie within it."""
        on = self._by_thread.get(outer.thread, [])
        i = bisect.bisect_left(self._thread_starts.get(outer.thread, []),
                               outer.start)
        out = []
        while i < len(on) and on[i].start <= outer.end:
            if on[i].end <= outer.end and on[i] is not outer:
                out.append(on[i])
            i += 1
        return out

    def next_on_thread(self, after: Span, name: str) -> Optional[Span]:
        return next((s for s in self._by_thread.get(after.thread, [])
                     if s.name == name and s.start >= after.end), None)

    def threads(self, device: int) -> set:
        """Threads that launched programs on `device`."""
        return {s.thread for s in self.named("runtime.launch")
                if s.args.get("device") == device}

    def innermost(self, thread: int, t: int,
                  program: bool = False) -> Optional[Span]:
        """The latest-starting span of `thread` open at `t`; with
        `program`, the harness's own spans left out."""
        best = None
        for s in self._by_thread.get(thread, []):
            if s.start > t:
                break
            if s.end >= t and not (
                    program and s.name.startswith(tr.SPAN_PREFIX)):
                best = s
        return best

    def scoped_ns(self, device: int, a: int, b: int, scope: str) -> int:
        ops = self.scoped.get(device, [])
        i = bisect.bisect_left(self._starts.get(device, []), a)
        total = 0
        while i < len(ops) and ops[i][1] < b:
            name, s, e = ops[i]
            if name == scope and e <= b:
                total += e - s
            i += 1
        return total


def scope_of(tf_op: str) -> Optional[str]:
    """``prefill`` or ``decode`` where `tf_op`'s path holds one."""
    for part in str(tf_op).rstrip(":").split("/"):
        if part in SCOPES:
            return part
    return None


def load(path: str) -> Program:
    """The program spans, the harness's stage-call spans and the scoped
    device operations of one ``.xplane.pb``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    meta = xplane.event_metadata(path)
    spans: List[Span] = []
    scoped: Dict[int, List[tr.Interval]] = {}
    thread = 0
    for plane in data.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            stats = meta.get(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = tr.leaves(
                        (scope_of(stats.get(e.name, {}).get("tf_op", "")),
                         int(e.start_ns), int(e.end_ns))
                        for e in line.events)
                    scoped[int(m.group(1))] = [iv for iv in ops if iv[0]]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        spans.append(Span(e.name, int(e.start_ns),
                                          int(e.end_ns), thread,
                                          dict(e.stats)))
                thread += 1
    spans.sort(key=lambda s: (s.start, -s.end))
    return Program(spans, scoped)


def qualify(prog: Optional[Program], device: int, t: int, name: str) -> str:
    """`name` of an idle gap on `device` whose middle is `t`, followed by
    the innermost program span then open on the device's replica
    thread: ``in_stage_call:runtime.fetch``."""
    if prog is None:
        return name
    open_ = [s for s in (prog.innermost(th, t, program=True)
                         for th in prog.threads(device)) if s is not None]
    if not open_:
        return name
    return f"{name}:{max(open_, key=lambda s: s.start).name}"


# -- per-call quantities ------------------------------------------------------
def _ready(run, prog: Optional[Program]) -> bool:
    return prog is not None and run.traced() and prog.has_spans()


def _calls(run, prog: Program) -> List[Tuple[Any, Span, Tuple[int, int]]]:
    """(call, its stage-call span, its device program) of every window
    call the trace holds whole."""
    out = []
    for c in run.window_calls():
        span, progs = prog.stage_call(c.index), run.call_programs(c)
        if span is not None and progs:
            out.append((c, span, progs[0]))
    return out


def _mean_ms(values_ns: List[float]) -> Optional[float]:
    return float(np.mean(values_ns)) * 1e-6 if values_ns else None


def phase_ms(run, prog: Optional[Program], scope: str) -> Optional[float]:
    """Mean device time of `scope`'s operations per window call."""
    if prog is None or not run.traced() or not any(prog.scoped.values()):
        return None
    return _mean_ms([prog.scoped_ns(c.device, a, b, scope)
                     for c, _, (a, b) in _calls(run, prog)])


def runtime_prep_ms(run, prog: Optional[Program]) -> Optional[float]:
    """Mean ``runtime.pad`` + ``put`` + ``launch`` per window call."""
    if not _ready(run, prog):
        return None
    prep = ("runtime.pad", "runtime.put", "runtime.launch")
    return _mean_ms([sum(s.end - s.start for s in prog.inside(span)
                         if s.name in prep)
                     for _, span, _ in _calls(run, prog)])


def runtime_fetch_ms(run, prog: Optional[Program]) -> Optional[float]:
    """Mean time from the end of a window call's device program to the
    end of its ``runtime.fetch``."""
    if not _ready(run, prog):
        return None
    out = []
    for _, span, (_, end) in _calls(run, prog):
        fetch = [s for s in prog.inside(span) if s.name == "runtime.fetch"]
        if fetch:
            out.append(fetch[-1].end - end)
    return _mean_ms(out)


def executor_host_ms(run, prog: Optional[Program]) -> Optional[float]:
    """Mean ``executor.complete`` of a window call plus the
    ``executor.form`` that follows it on the replica's thread."""
    if not _ready(run, prog):
        return None
    out = []
    for _, span, _ in _calls(run, prog):
        batch = [b for b in prog.named("executor.batch")
                 if b.thread == span.thread and b.start <= span.start
                 and span.end <= b.end]
        if not batch:
            continue
        done = [s for s in prog.inside(batch[0])
                if s.name == "executor.complete"]
        form = prog.next_on_thread(batch[0], "executor.form")
        if done and form is not None:
            out.append(done[0].end - done[0].start + form.end - form.start)
    return _mean_ms(out)


def inject_lag_ms(run, prog: Optional[Program]) -> Optional[float]:
    """p99 injection lag of the requests due in the window."""
    if not _ready(run, prog):
        return None
    due = run.due()
    lags = [s.args["lag_us"] for s in prog.named("executor.inject")
            if 0 <= int(s.args.get("rid", -1)) < due.size
            and due[int(s.args["rid"])]]
    return float(np.percentile(lags, 99.0)) * 1e-3 if lags else None
