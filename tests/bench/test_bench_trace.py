"""Trace reduction: busy union, idle share, time by operation and
kernel, device time per stage call, and naming of idle gaps."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import flops, harness, trace as tr  # noqa: E402
from bench.families import dense  # noqa: E402
from bench.record import Call, Run, host_ms, kernel_roofline_pct, step_ms  # noqa: E402
from bench.spec import BENCH_DIR, load_json, metric_reader  # noqa: E402

MS = 1_000_000


def test_merge_busy_and_gaps():
    ops = [("a", 0, 10), ("b", 5, 20), ("a", 30, 40), ("c", 35, 38)]
    merged = tr.merge(ops)
    assert merged == [(0, 20), (30, 40)]
    assert tr.busy_ns(merged, 0, 50) == 30
    assert tr.busy_ns(merged, 10, 35) == 15
    assert tr.gaps(merged, 0, 50) == [(20, 30), (40, 50)]
    assert tr.gaps(merged, 15, 32) == [(20, 30)]
    assert tr.time_by_kind(ops, 0, 50) == pytest.approx(
        {"a": 20e-9, "b": 15e-9, "c": 3e-9})


def _synthetic_run():
    """One device; two stage calls, each a host span around one program
    holding one flash and seven decode operations."""
    cfg = load_json(BENCH_DIR / "configs" / "phi3-mini-3.8b.json")
    cfg = dict(cfg, num_hidden_layers=1)
    ops, modules, spans = [], [], {}
    for k, t in enumerate((10 * MS, 40 * MS)):
        spans[k] = (t - (9 * MS if k == 0 else 0), t + 20 * MS)
        modules.append(("jit_generate", t + 2 * MS, t + 17 * MS))  # 15 ms
        ops.append(("fusion", t + 2 * MS, t + 5 * MS))
        ops.append(("flash_attention", t + 5 * MS, t + 6 * MS))
        for j in range(7):
            s = t + 6 * MS + j * MS // 2
            ops.append(("decode_attention", s, s + MS // 2))
        ops.append(("fusion", t + 9 * MS + MS // 2, t + 17 * MS))
    trace = tr.Trace({0: ops}, {0: modules}, spans)
    offset = 1_000 * MS                                # trace = host + 1 s
    run = Run(config=cfg, family=dense,
              peak=harness.load_peak("TPU v5 lite"), prompt=128, gen=8,
              buckets=(1, 2, 4), arrival=np.array([0.0, 0.0005, 0.02]),
              started=np.array([0.001, 0.001, 0.04]),
              done=np.array([0.03, 0.03, 0.06]), w0=0.0, w1=0.07,
              calls=[Call(0, 0, 0.001, 0.030, 2), Call(1, 0, 0.040, 0.060, 1)],
              trace=trace)
    for k in spans:
        spans[k] = (spans[k][0] + offset, spans[k][1] + offset)
    for lst in (ops, modules):
        lst[:] = [(n, a + offset, b + offset) for n, a, b in lst]
    run.offset_ns = tr.clock_offset_ns(trace, {c.index: c.t0 for c in run.calls})
    return run


def test_per_call_arithmetic():
    run = _synthetic_run()
    assert run.offset_ns == pytest.approx(1_000 * MS)
    assert step_ms(run) == pytest.approx(15.0)
    assert host_ms(run) == pytest.approx((14.0 + 5.0) / 2)
    c = run.calls[0]
    assert run.kernel_s(c, "flash_attention") == pytest.approx(1e-3)
    assert run.kernel_s(c, "decode_attention") == pytest.approx(3.5e-3)
    m, peak = dense.dims(run.config), run.peak
    want = flops.roofline_s(*dense.flash_call(m, 2, 128), peak) \
        + flops.roofline_s(*dense.flash_call(m, 1, 128), peak)
    assert kernel_roofline_pct(run, "flash_attention") == pytest.approx(
        100 * want / 2e-3)
    assert metric_reader("step_ms.overload")(run) == pytest.approx(15.0)
    assert metric_reader("runtime_host_ms.overload")(run) == pytest.approx(
        (14.0 + 5.0) / 2)
    mfu = metric_reader("step_mfu_pct")(run)
    assert mfu == pytest.approx(100 * 3 * dense.request_flops(
        run.config, 128, 8) / (30e-3 * peak["bf16_flops_per_s"]))
    # busy: 2 x 15 ms of the 70 ms window
    assert metric_reader("device_idle_pct")(run) == pytest.approx(
        100 * (1 - 30 / 70))
    # what each reader gave before the counts moved into the family
    assert _readings(run) == pytest.approx(SYNTHETIC_READINGS, rel=1e-12)


READERS = ("step_ms.overload", "runtime_host_ms.overload", "step_mfu_pct",
           "flash_attention_roofline", "decode_attention_roofline",
           "device_idle_pct")
SYNTHETIC_READINGS = {
    "step_ms.overload": 15.000000000000002,
    "runtime_host_ms.overload": 9.5,
    "step_mfu_pct": 1.637831965888325,
    "flash_attention_roofline": 1.1522813186813186,
    "decode_attention_roofline": 1.1972923076923074,
    "device_idle_pct": 57.14285714285714}
# the readers over the recorded phi3 trace below, as the harness read
# them when its operation counts lived in bench/flops.py
RECORDED_READINGS = {
    "step_ms.overload": 14.524617000000003,
    "runtime_host_ms.overload": 2.5848469999999995,
    "step_mfu_pct": 3.300255804992527,
    "flash_attention_roofline": 40.56043221096549,
    "decode_attention_roofline": 25.89467975198017,
    "device_idle_pct": 97.1988561}


def _readings(run):
    return {name: metric_reader(name)(run) for name in READERS}


def test_readers_over_four_devices():
    """One replica per device, as on a four-chip host: every device's
    calls count, and the idle share is the devices' mean."""
    cfg = dict(load_json(BENCH_DIR / "configs" / "phi3-mini-3.8b.json"),
               num_hidden_layers=1)
    ops, modules, spans, calls = {}, {}, {}, []
    for dev in range(4):
        ops[dev], modules[dev] = [], []
        for j in range(2):
            k = 2 * dev + j
            t = (10 + 30 * j) * MS + dev * MS
            prog = (2 + dev) * MS                  # device d: 2 + d ms
            spans[k] = (t, t + prog + 3 * MS)      # 3 ms of host a call
            modules[dev].append(("jit_generate", t + MS, t + MS + prog))
            ops[dev].append(("fusion", t + MS, t + MS + prog))
            calls.append(Call(k, dev, t * 1e-9, (t + prog + 3 * MS) * 1e-9,
                              1 + dev))
    run = Run(config=cfg, family=dense,
              peak=harness.load_peak("TPU v5 lite"),
              prompt=128, gen=8, buckets=(1, 2, 4), arrival=np.zeros(1),
              started=np.zeros(1), done=np.zeros(1), w0=0.0, w1=0.08,
              calls=calls, trace=tr.Trace(ops, modules, spans))
    run.offset_ns = tr.clock_offset_ns(run.trace,
                                       {c.index: c.t0 for c in calls})
    assert run.devices() == [0, 1, 2, 3]
    assert metric_reader("runtime_host_ms.overload")(run) == pytest.approx(
        3.0)
    assert metric_reader("step_ms.overload")(run) == pytest.approx(
        (2 + 3 + 4 + 5) / 4)
    # device d is busy 2 x (2 + d) ms of the 80 ms window
    assert metric_reader("device_idle_pct")(run) == pytest.approx(
        100 * (1 - sum(2 * (2 + d) for d in range(4)) / 4 / 80))
    busy = sum(2 * (2 + d) * 1e-3 for d in range(4))
    assert metric_reader("step_mfu_pct")(run) == pytest.approx(
        100 * 2 * (1 + 2 + 3 + 4) * dense.request_flops(cfg, 128, 8)
        / (busy * run.peak["bf16_flops_per_s"]))


def test_programs_pair_in_order_despite_clock_skew():
    """Four devices whose clock reads up to 0.57 ms early against the
    host's spans, as measured on a TPU v5e: a program can start before
    its own span and inside the one before it. Each device first ran 5
    warm-up programs, and the trace stopped inside a last call, whose
    span it does not hold. Every traced call pairs with its own
    program."""
    cfg = dict(load_json(BENCH_DIR / "configs" / "phi3-mini-3.8b.json"),
               num_hidden_layers=1)
    us = 1_000
    ops, modules, spans, calls, want = {}, {}, {}, [], {}
    k = 0
    for dev in range(4):
        ops[dev], modules[dev] = [], []
        t = (10 + dev) * MS                    # host ns
        for b in range(5):                     # warm-up, no span
            prog = (2 + b) * MS
            a = t - 570 * us
            modules[dev].append((f"warm{b}", a, a + prog))
            t += prog + 50 * us
        for j in range(6):
            prog = (5 + 3 * j + dev) * MS      # a length of its own
            launch = (0, 300, 940)[j % 3] * us
            a = t + launch - 570 * us          # on the device's clock
            spans[k] = (t, t + launch + prog + 800 * us)
            modules[dev].append((f"call{k}", a, a + prog))
            ops[dev].append(("fusion", a, a + prog))
            calls.append(Call(k, dev, t * 1e-9, spans[k][1] * 1e-9, 1))
            want[k] = (a, a + prog)
            t = spans[k][1] + 250 * us          # the executor between calls
            k += 1
        modules[dev].append(("cut", t - 570 * us, t + 3 * MS))
    trace = tr.Trace(ops, modules, spans)
    got = tr.assign_programs(trace, [(c.index, c.device) for c in calls])
    assert got == want
    # the fault it repairs: programs that start before their own span,
    # and spans that hold the next call's program
    assert sum(a < spans[k][0] for k, (a, _) in want.items()) >= 8
    assert sum(spans[k][0] <= want[k + 1][0] <= spans[k][1]
               for k in want if k + 1 in want) >= 4
    run = Run(config=cfg, family=dense, peak=harness.load_peak("TPU v5 lite"),
              prompt=128, gen=8, buckets=(1,), arrival=np.zeros(1),
              started=np.zeros(1), done=np.zeros(1), w0=0.0, w1=1.0,
              calls=calls, trace=trace)
    run.offset_ns = tr.clock_offset_ns(trace, {c.index: c.t0 for c in calls})
    assert metric_reader("step_ms.overload")(run) == pytest.approx(
        np.mean([(e - a) / MS for a, e in want.values()]))


def test_breakdown_names_gaps():
    run = _synthetic_run()
    b = harness.breakdown(run)
    assert b["device_ops"][0] == ["fusion", pytest.approx(21e-3)]
    names = {n for n, _ in b["idle_gaps"]}
    # idle 0-12 ms (inside call 0's span, from 1 ms), 27-42 ms (request
    # 2, due at 20 ms, waits for the batch at 40 ms), 57-70 ms (nothing)
    assert names == {"in_stage_call", "between_stage_calls.queued",
                     "between_stage_calls.no_request"}
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(40e-3)


def test_untraced_run_reads_nothing():
    run = _synthetic_run()
    run.trace = None
    for name in ("step_ms.overload", "runtime_host_ms.overload",
                 "step_mfu_pct", "flash_attention_roofline",
                 "decode_attention_roofline", "device_idle_pct"):
        assert metric_reader(name)(run) is None


def test_leaves_drop_control_flow():
    ops = [("while", 0, 100), ("fusion", 0, 10), ("while", 20, 90),
           ("flash_attention", 25, 40), ("fusion", 40, 90), ("copy", 100, 110)]
    assert tr.leaves(ops) == [("fusion", 0, 10), ("flash_attention", 25, 40),
                              ("fusion", 40, 90), ("copy", 100, 110)]


def test_op_names():
    assert tr.op_name("%bitcast_add_fusion.3 = f32[2] fusion(...)", {}) \
        == "bitcast_add_fusion"
    assert tr.op_name("%closed_call.63 = f32[1,48,128,128] custom-call()", {
        "tf_op": "jit(_generate_body)/while/body/closed_call/pallas_call:",
        "source": "/x/src/repro/kernels/flash_attention.py:111"}) \
        == "flash_attention"


DATA = Path(__file__).resolve().parent / "data"


def test_recorded_chip_trace():
    """Two stage calls of granite-34b (4 layers) at batch 1 and 2,
    recorded on a TPU v5e with the harness's spans."""
    t = tr.load(str(DATA / "granite34b_two_calls.xplane.pb"))
    assert sorted(t.spans) == [0, 1] and list(t.ops) == [0]
    progs = tr.assign_programs(t, [(0, 0), (1, 0)])
    for k in (0, 1):        # each program runs inside its call's span
        (a, b), (s, e) = t.spans[k], progs[k]
        assert a <= s < e <= b
    assert (progs[0][1] - progs[0][0]) * 1e-6 == pytest.approx(88.614, abs=1e-3)
    assert (progs[1][1] - progs[1][0]) * 1e-6 == pytest.approx(59.867, abs=1e-3)
    names = [n for n, _, _ in t.ops[0]]
    # 4 layers: one flash call each in prefill; 7 decode steps x 4 layers;
    # rmsnorm twice a layer and once at the head, in prefill and each step
    assert names.count("flash_attention") == 2 * 4
    assert names.count("decode_attention") == 2 * 7 * 4
    assert names.count("rmsnorm") == 2 * (4 * 2 + 1) * 8
    assert "while" not in names and "convert" in names
    busy = tr.busy_ns(tr.merge(t.ops[0]), progs[0][0], progs[1][1])
    inside = sum(e - s for s, e in progs.values())
    assert 0.9 * inside < busy <= inside
    # every reader over two stage calls of phi3-mini-3.8b at 2 layers
    # (batch 1 and 2), recorded on a TPU v5e, gives what it gave before
    path = str(DATA / "phi3_two_layers_spans.xplane.pb")
    with open(DATA / "phi3_two_layers_spans.calls.json") as f:
        recorded = {int(k): v for k, v in json.load(f).items()}
    cfg = dict(load_json(BENCH_DIR / "configs" / "phi3-mini-3.8b.json"),
               num_hidden_layers=2)
    trace = tr.load(path)
    run = Run(config=cfg, family=dense,
              peak=harness.load_peak("TPU v5 lite"),
              prompt=128, gen=8, buckets=(1, 2), arrival=np.zeros(3),
              started=np.zeros(3), done=np.zeros(3), w0=0.0, w1=1.0,
              calls=[Call(k, c["device"], c["t0"], c["t1"], c["rows"])
                     for k, c in sorted(recorded.items())], trace=trace)
    run.offset_ns = tr.clock_offset_ns(
        trace, {k: c["t0"] for k, c in recorded.items()})
    assert _readings(run) == pytest.approx(RECORDED_READINGS, rel=1e-12)
