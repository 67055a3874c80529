"""The program's spans and phase scopes read from a trace: per-call
quantities on a synthetic run, nothing read where the program wrote
none, and the spans of a recorded chip trace."""

import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spans as sp, trace as tr, xplane  # noqa: E402
from bench.record import Call, Run  # noqa: E402

MS = 1_000_000
OFFSET = 1_000 * MS                     # trace clock = host clock + 1 s
DATA = Path(__file__).resolve().parent / "data"


def _call(k, t, thread=1):
    """Spans and device ops of stage call `k` whose stage-call span
    starts at `t` (trace ns): prep 0.9 ms, program t+2..t+17 ms (4 ms
    prefill, 10 ms decode), fetch ends 1 ms after the program, complete
    1.5 ms, the next formation 0.8 ms."""
    S = lambda name, a, b, **args: sp.Span(  # noqa: E731
        name, t + int(a * MS), t + int(b * MS), thread, args)
    spans = [
        S("executor.batch", -1, 25, stage="s", rows=2, rid0=2 * k,
          t=(t - MS - OFFSET) * 1e-9),
        S(f"stage_call#{k}", 0, 22),
        S("runtime.pad", 0.1, 0.3, bucket=2, rows=2),
        S("runtime.put", 0.3, 0.6),
        S("runtime.launch", 0.6, 1.0, device=0),
        S("runtime.fetch", 1.2, 18),
        S("executor.complete", 22.5, 24),
        S("executor.form", 25.2, 26, rows=2, wait_ms=3.0),
    ]
    ops = [("prefill", t + 2 * MS, t + 6 * MS),
           ("decode", t + 6 * MS, t + 10 * MS),
           ("decode", t + 11 * MS, t + 17 * MS)]
    return spans, ops, ("jit_generate", t + 2 * MS, t + 17 * MS)


def _run(n_calls=2):
    starts = [OFFSET + 10 * MS + k * 30 * MS for k in range(n_calls)]
    spans, ops, modules = [], [], []
    for k, t in enumerate(starts):
        s, o, m = _call(k, t)
        spans += s
        ops += o
        modules.append(m)
    lags = [5.0, 50.0, 20.0, 100.0, 7000.0]      # us; the last falls late
    spans += [sp.Span("executor.inject", OFFSET + i * MS, OFFSET + i * MS + 10,
                      0, {"rid": i, "lag_us": lag})
              for i, lag in enumerate(lags)]
    spans.sort(key=lambda s: (s.start, -s.end))
    trace = tr.Trace({0: [(n, a, b) for n, a, b in ops]}, {0: modules},
                     {k: (t, t + 22 * MS) for k, t in enumerate(starts)})
    run = Run(config={}, family=None, peak={}, prompt=128, gen=8,
              buckets=(1, 2), arrival=np.array([0.0, 0.001, 0.002, 0.003,
                                                0.2]),
              started=np.full(5, 0.01), done=np.full(5, 0.05), w0=0.0,
              w1=0.1, calls=[Call(k, 0, (t - OFFSET) * 1e-9,
                                  (t + 22 * MS - OFFSET) * 1e-9, 2)
                             for k, t in enumerate(starts)],
              trace=trace)
    run.offset_ns = tr.clock_offset_ns(trace, {c.index: c.t0
                                               for c in run.calls})
    return run, sp.Program(spans, {0: ops})


def test_per_call_quantities():
    run, prog = _run()
    assert sp.phase_ms(run, prog, "prefill") == pytest.approx(4.0)
    assert sp.phase_ms(run, prog, "decode") == pytest.approx(10.0)
    assert sp.runtime_prep_ms(run, prog) == pytest.approx(0.9)
    assert sp.runtime_fetch_ms(run, prog) == pytest.approx(1.0)
    assert sp.executor_host_ms(run, prog) == pytest.approx(1.5 + 0.8)
    # requests 0-3 are due in the window; request 4 (7 ms late) is not
    assert sp.inject_lag_ms(run, prog) == pytest.approx(
        np.percentile([5.0, 50.0, 20.0, 100.0], 99) * 1e-3)


def test_gaps_named_by_the_open_program_span():
    run, prog = _run()
    t = run.calls[0].t0 * 1e9 + OFFSET
    assert sp.qualify(prog, 0, t + 1.1 * MS, "in_stage_call") == \
        "in_stage_call:executor.batch"      # between launch and fetch
    assert sp.qualify(prog, 0, t + 25.1 * MS, "x") == "x"   # none open
    assert sp.qualify(prog, 0, t + 10.5 * MS, "in_stage_call") == \
        "in_stage_call:runtime.fetch"
    assert sp.qualify(prog, 0, t + 23 * MS, "between_stage_calls.queued") \
        == "between_stage_calls.queued:executor.complete"
    assert sp.qualify(prog, 0, t + 25.5 * MS, "x") == "x:executor.form"
    assert sp.qualify(prog, 1, t + 23 * MS, "x") == "x"   # no such device
    assert sp.qualify(None, 0, t + 23 * MS, "x") == "x"
    inner = prog.innermost(1, int(t + 10 * MS))
    assert inner.name == "runtime.fetch"
    assert [s.name for s in prog.inside(prog.stage_call(0))] == [
        "runtime.pad", "runtime.put", "runtime.launch", "runtime.fetch"]


READERS = [lambda r, p: sp.phase_ms(r, p, "prefill"),
           lambda r, p: sp.phase_ms(r, p, "decode"), sp.runtime_prep_ms,
           sp.runtime_fetch_ms, sp.executor_host_ms, sp.inject_lag_ms]


@pytest.mark.parametrize("reader", READERS, ids=[
    "prefill_ms", "decode_ms", "runtime_prep_ms", "runtime_fetch_ms",
    "executor_host_ms", "inject_lag_ms"])
def test_nothing_read_without_program_spans(reader):
    run, prog = _run()
    assert reader(run, None) is None
    assert reader(run, sp.Program([], {})) is None
    untraced, _ = _run()
    untraced.trace = None
    assert reader(untraced, prog) is None
    # a chip trace of a program that wrote no spans or scopes
    parent = sp.load(str(DATA / "granite34b_two_calls.xplane.pb"))
    assert [s.name for s in parent.spans] == ["stage_call#0", "stage_call#1"]
    assert not parent.has_spans() and not any(parent.scoped.values())
    assert reader(run, parent) is None


def test_scope_of():
    assert sp.scope_of("jit(_generate_body)/prefill/closed_call/"
                       "flash_attention/pallas_call:") == "prefill"
    assert sp.scope_of("jit(_generate_body)/decode/while/body/dot:") == \
        "decode"
    assert sp.scope_of("jit(_generate_body)/while/body/dot:") is None
    assert sp.scope_of("") is None


def test_recorded_chip_trace_with_program_spans():
    """Stage calls of phi3-mini-3.8b at 2 layers, recorded on a TPU v5e
    with the program's spans and scopes and the harness's stage-call
    spans; the calls' host starts beside it."""
    path = str(DATA / "phi3_two_layers_spans.xplane.pb")
    prog, t = sp.load(path), tr.load(path)
    meta = xplane.event_metadata(path)["/device:TPU:0"]
    ops = [(e.name, int(e.start_ns), int(e.end_ns))
           for p in jax.profiler.ProfileData.from_file(path).planes
           if p.name == "/device:TPU:0" for line in p.lines
           if line.name == "XLA Ops" for e in line.events]
    ops = tr.leaves(ops)
    with open(DATA / "phi3_two_layers_spans.calls.json") as f:
        calls = {int(k): v for k, v in json.load(f).items()}
    assert len(calls) >= 2 and sorted(t.spans) == sorted(calls)
    progs = tr.assign_programs(t, [(k, 0) for k in calls])
    for k, c in calls.items():
        call = prog.stage_call(k)
        (batch,) = [b for b in prog.named("executor.batch")
                    if b.thread == call.thread and b.start <= call.start
                    and call.end <= b.end]
        assert batch.args["rows"] == c["rows"]
        assert [s.name for s in prog.inside(call)] == [
            "runtime.pad", "runtime.put", "runtime.launch", "runtime.fetch"]
        assert [s.name for s in prog.inside(batch)
                if s.name == "executor.complete" and s.start >= call.end] \
            == ["executor.complete"]
        # the device program runs inside the call, before its fetch
        # ends (the device's clock is not the host's to the microsecond:
        # programs show up to ~0.6 ms before the launch span starts)
        fetch = prog.inside(call)[-1]
        a, b = progs[k]
        assert call.start <= a < b < fetch.end <= call.end
        # every operation the program's code made is in one of the two
        # scopes; outside them are only what XLA made without metadata,
        # the weights' convert to bf16 hoisted to the program's start and
        # the async slices of the layer loop
        pre, dec = (prog.scoped_ns(0, a, b, s) for s in sp.SCOPES)
        bare = sum(e - s for n, s, e in ops if a <= s and e <= b
                   and not meta.get(n, {}).get("tf_op"))
        leaves = sum(e - s for _, s, e in t.ops[0] if a <= s and e <= b)
        assert pre > 0 and dec > pre and pre + dec + bare == leaves
    injects = prog.named("executor.inject")
    assert {s.thread for s in injects}.isdisjoint(
        {b.thread for b in prog.named("executor.batch")})
    # the batch's executor-clock `t` lands on the trace's clock through
    # the offset the harness computes from its own spans
    off = tr.clock_offset_ns(t, {k: c["t0"] for k, c in calls.items()})
    for b in prog.named("executor.batch"):
        assert abs(b.start - b.args["t"] * 1e9 - off) < 0.2e6
    names = [n for n, _, _ in t.ops[0]]
    assert names.count("flash_attention") == 2 * len(calls)
    assert names.count("decode_attention") == 2 * 7 * len(calls)
