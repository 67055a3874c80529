"""Run one cell: set up, serve the window, check the answers, report.

1. Build the configuration the cell's data fixes: the registry's model
   cut as the file says (by its family, ``bench/families/``), weights
   from the seed, a ``StageRuntime`` with every bucket compiled and run
   once, and a one-stage pipeline whose batch cap, replicas and batch
   timeout come from the file. Nothing is profiled or planned here.
2. Serve the mix open loop through ``PipelineExecutor.serve_trace``: a
   warm-up segment (``warmup_s``), then the measured window of
   ``seconds``. The window holds the requests whose nominal arrival
   falls inside it; latency runs from that due time.
3. With the program's state freed, run the family's plain reference
   over a sample of the window's requests, drawn from the seed before
   serving, and compare it with their served tokens and with the logits
   the timed path computed for them (:func:`judge`).
"""

from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from bench import arrivals as gen, reference, stats, trace as tr
from bench.record import Call, Run
from bench.serve import StageRecorder, seeded_runtime, warm
from bench.spec import BENCH_DIR, Cell, metric_reader
from bench.weights import base_key, make_params
from repro.configs import get_arch
from repro.core.hardware import hardware_for_device
from repro.core.pipeline import PipelineConfig, StageConfig, linear_pipeline
from repro.models import build_model
from repro.serving.executor import PipelineExecutor
from repro.serving.runtime import GEN_TOKENS

TOP = 10        # entries of each breakdown list
# the traced stretch: the window, and this much on either side, so that
# every call the window holds is whole in the trace
TRACE_MARGIN_S = 1.0


class NoChip(RuntimeError):
    pass


def find_devices(chips: int) -> List:
    """The first `chips` TPU devices; never falls back to the CPU."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def load_peak(kind: str) -> Dict[str, Any]:
    with open(BENCH_DIR / "peaks.json") as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r}; have "
                       f"{sorted(peaks)}")
    return peaks[kind]


class Compiles:
    """JAX monitoring listener: XLA compiles while it is registered."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds: List[float] = []

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.seconds.append(duration)


class GcPauses:
    """``gc.callbacks`` hook: how long each collection held the process."""

    def __init__(self):
        self.seconds: List[float] = []
        self._t0 = 0.0

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.seconds.append(time.perf_counter() - self._t0)


def build_runtime(config: Dict[str, Any], family, key, devices: List,
                  registry: Callable = get_arch):
    """(arch, runtime): the model as its `family` builds it from
    `config`, weights from `key` drawn by the family's ``init_rule``,
    every bucket compiled and run once on every device."""
    arch = family.build_arch(config, registry)
    shapes = jax.eval_shape(build_model(arch).init, jax.random.PRNGKey(0))
    srv = config["serving"]
    rt = seeded_runtime(arch, devices, int(srv["prompt_tokens"]),
                        int(srv["max_batch"]),
                        lambda: make_params(key, shapes, family.init_rule))
    warm(rt)
    return arch, rt


def make_executor(name: str, config: Dict[str, Any], rt, devices: List,
                  stage_fn) -> PipelineExecutor:
    """A one-stage pipeline served as the configuration file fixes it:
    batch cap, replicas per chip and batch timeout."""
    srv = config["serving"]
    hw = hardware_for_device(devices[0])
    model_id = config["registry_id"]
    pipe = linear_pipeline(name, [model_id], {model_id: [hw]})
    (stage,) = pipe.stages
    return PipelineExecutor(pipe, PipelineConfig({stage: StageConfig(
        hw, rt.max_batch, int(srv["replicas_per_chip"]) * len(devices),
        timeout_s=float(srv["batch_timeout_s"]))}), {model_id: stage_fn})


def _answer_ok(out: Any, vocab: int) -> bool:
    return (isinstance(out, np.ndarray) and out.shape == (GEN_TOKENS,)
            and out.dtype == np.int32
            and bool(((out >= 0) & (out < vocab)).all()))


def profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1     # the harness's own spans, little else
    return opts


class WindowTrace:
    """A profiler trace of ``[t0, t1)`` on an executor's clock, started
    and stopped from a thread of its own while the executor serves, so
    the warm-up and the drain are left out of it."""

    def __init__(self, logdir: str, clock: Callable[[], float], t0: float,
                 t1: float, log=print):
        self.logdir, self.clock, self.t0, self.t1 = logdir, clock, t0, t1
        self.log = log
        self.error: Optional[BaseException] = None
        self._done = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def begin(self) -> None:
        """Start waiting for ``t0``; call once the clock is zeroed."""
        self._thread.start()

    def _wait_until(self, t: float) -> bool:
        """Whether the clock reached `t` before :meth:`finish` was asked."""
        while not self._done.is_set():
            left = t - self.clock()
            if left <= 0:
                return True
            self._done.wait(min(left, 0.05))
        return False

    def _run(self) -> None:
        try:
            if not self._wait_until(self.t0):
                return
            t = time.perf_counter()
            jax.profiler.start_trace(self.logdir,
                                     profiler_options=profile_options())
            started = time.perf_counter() - t
            self._wait_until(self.t1)
            t, end = time.perf_counter(), self.clock()
            jax.profiler.stop_trace()
            self.log(f"[trace] {self.t0:.1f}-{end:.1f} s traced; start "
                     f"{started:.2f} s, stop {time.perf_counter() - t:.1f} s")
        except BaseException as e:      # re-raised by finish()
            self.error = e

    def finish(self) -> None:
        """Stop the trace now if ``t1`` has not come, and wait for it."""
        self._done.set()
        if self._thread.ident is not None:
            self._thread.join()
        if self.error is not None:
            raise self.error


def breakdown(run: Run) -> Dict[str, List]:
    """The window's costliest device operations and longest idle gaps,
    each gap named by what the host was doing in its middle."""
    w0, w1 = run.window_ns()
    ops: Dict[str, float] = {}
    for d in run.devices():
        for k, s in tr.time_by_kind(run.trace.ops[d], w0, w1).items():
            ops[k] = ops.get(k, 0.0) + s
    spans = [(run.trace.spans[c.index], c.device) for c in run.calls
             if c.index in run.trace.spans]
    to_host = lambda ns: (ns - run.offset_ns) * 1e-9  # noqa: E731
    longest = sorted(((b - a, a, b, d) for d in run.devices()
                      for a, b in tr.gaps(tr.merge(run.trace.ops[d]), w0, w1)),
                     reverse=True)[:TOP]
    gaps = []
    for length, a, b, d in longest:
        mid = (a + b) // 2
        if any(s <= mid <= e and dev == d for (s, e), dev in spans):
            name = "in_stage_call"
        else:
            t = to_host(mid)
            waiting = np.any((run.arrival <= t) & ~(run.started <= t))
            name = ("between_stage_calls.queued" if waiting
                    else "between_stage_calls.no_request")
        gaps.append([name, length * 1e-9])
    return {"device_ops": sorted(([k, v] for k, v in ops.items()),
                                 key=lambda kv: -kv[1])[:TOP],
            "idle_gaps": gaps}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices: List, t_start: float, registry: Callable = get_arch,
             fault: Optional[Callable] = None, log=print) -> Dict[str, Any]:
    """One run of `cell`; returns the result line's object.

    ``fault``, for tests, wraps the runtime's stage fn to break the
    timed path underneath the harness.
    """
    cfg, traffic = cell.config, cell.traffic
    srv = cfg["serving"]
    prompt, vocab = int(srv["prompt_tokens"]), int(cfg["vocab_size"])
    if int(srv["gen_tokens"]) != GEN_TOKENS:
        raise ValueError(f"the runtime generates {GEN_TOKENS} tokens, the "
                         f"file says {srv['gen_tokens']}")
    key = base_key(seed)
    arch, rt = build_runtime(cfg, cell.family, key, devices, registry)

    arrival = gen.run_arrivals(traffic, seed, seconds)
    n = arrival.size
    prompts = gen.rng(seed, gen.PROMPTS_STREAM).integers(
        0, vocab, (n, prompt), dtype=np.int32)
    plist = list(prompts)
    w0 = float(traffic["warmup_s"])
    w1 = w0 + seconds
    sample = check_sample(cfg, seed, arrival, w0, w1)
    rec = StageRecorder(fault(rt) if fault else rt, rt,
                        {id(p): i for i, p in enumerate(plist)}, keep=sample)
    ex = make_executor(cell.name, cfg, rt, devices, rec)
    rec.clock = ex.now
    replicas = ex.replica_target(next(iter(ex.pipeline.stages)))
    answers: List[Any] = [None] * n
    ex.on_request_done = lambda req: answers.__setitem__(req.rid, req.payload)
    # A full collection scans every object the process holds, JAX's
    # included, and stalls every thread for a second or more: freeze the
    # set-up heap so the window's collections see only new objects.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"[setup] {cell.name}: {arch.num_layers} layers, buckets "
        f"{rt.buckets}, {replicas} replica(s), {n} requests, "
        f"setup {setup_s:.1f}s")

    window = None
    if trace:
        window = WindowTrace(tempfile.mkdtemp(prefix="bench_trace_"), ex.now,
                             w0 - TRACE_MARGIN_S, w1 + TRACE_MARGIN_S, log)
        start_run = ex.start_run

        def start_run_and_trace():
            start_run()             # zeroes the clock the trace follows
            window.begin()
        ex.start_run = start_run_and_trace
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        lat = ex.serve_trace(arrival, lambda i: plist[i],
                             timeout_s=float(traffic["drain_s"]))
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
        gc.callbacks.remove(pauses)
        try:
            if window:
                window.finish()
        finally:
            ex.shutdown()
    memory_peak = max(int(d.memory_stats().get("peak_bytes_in_use", 0))
                      if d.memory_stats() else 0 for d in devices)
    injection = ex.injection_stats() or {}
    calls = [Call(k, *c) for k, c in enumerate(rec.calls) if c is not None]
    started = rec.started.copy()
    logits = rec.kept_logits(sample, (GEN_TOKENS, vocab))
    # free the program's state before the reference runs
    rec.fn = rec.runtime = None
    rec.kept.clear()
    del ex, rt, rec
    gc.unfreeze()
    gc.collect()

    run = Run(config=cfg, family=cell.family,
              peak=load_peak(devices[0].device_kind)
              if devices[0].platform == "tpu" else {},
              prompt=prompt, gen=GEN_TOKENS, buckets=tuple(
                  1 << i for i in range((int(srv["max_batch"]) - 1)
                                        .bit_length() + 1)),
              arrival=arrival, started=started, done=arrival + lat,
              w0=w0, w1=w1, calls=calls)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    out: Dict[str, Any] = {}
    if trace:
        t0 = time.perf_counter()
        run.trace = tr.load(tr.find_xplane(window.logdir))
        shutil.rmtree(window.logdir, ignore_errors=True)
        log(f"[trace] {sum(map(len, run.trace.ops.values()))} operations, "
            f"{len(run.trace.spans)} stage calls, read in "
            f"{time.perf_counter() - t0:.1f}s")
        run.offset_ns = tr.clock_offset_ns(
            run.trace, {c.index: c.t0 for c in calls})
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m.name)(run)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.unit}
        if run.traced():
            wn0, wn1 = run.window_ns()
            busy = [tr.busy_ns(tr.merge(run.trace.ops[d]), wn0, wn1) * 1e-9
                    for d in run.devices()]
            device["busy_s"] = float(np.mean(busy)) if busy else 0.0
            device["window_s"] = (wn1 - wn0) * 1e-9
            out["breakdown"] = breakdown(run)
    else:
        e2e = stats.end_to_end(arrival, arrival + lat, w0, w1)
        e2e["setup_s"] = setup_s
        metrics = {m.name: {"value": e2e[m.name], "unit": m.unit}
                   for m in cell.end_to_end}

    # -- correctness --------------------------------------------------------
    due = np.flatnonzero(run.due())
    failed = sum(not _answer_ok(answers[i], vocab) for i in due)
    served = np.stack([answers[i] if _answer_ok(answers[i], vocab)
                       else np.zeros(GEN_TOKENS, np.int32) for i in sample]
                      ) if sample.size else np.zeros((0, GEN_TOKENS), np.int32)
    t0 = time.perf_counter()
    with jax.default_device(devices[0]):
        ref = cell.family.logits(cfg, key, reference.teacher_forced(
            prompts[sample], served), GEN_TOKENS) if sample.size else None
    log(f"[check] reference over {sample.size} requests "
        f"({served.size} served tokens) took {time.perf_counter() - t0:.1f}s")
    checks = judge(cfg, ref, served, logits, failed)
    lat_due = stats.latency_ms(arrival, arrival + lat, w0, w1)
    longest = max(calls, key=lambda c: c.t1 - c.t0)
    log(f"[serve] due in window {len(due)}, answered {len(due) - failed}, "
        f"attainment {stats.attainment_pct(lat_due, traffic['slo_ms']):.2f}% "
        f"of slo {traffic['slo_ms']} ms, p50 "
        f"{stats.percentile(lat_due, 50):.2f} ms, p95 "
        f"{stats.percentile(lat_due, 95):.2f} ms, p99 "
        f"{stats.percentile(lat_due, 99):.2f} ms, throughput "
        f"{stats.throughput_rps(arrival + lat, w0, w1):.3f} req/s, "
        f"batches {len(calls)}, mean rows "
        f"{np.mean([c.rows for c in calls]) if calls else 0:.2f}, "
        f"injection lag p99 {injection.get('p99_lag_s', 0) * 1e3:.2f} ms "
        f"max {injection.get('max_lag_s', 0) * 1e3:.2f} ms, "
        f"{len(pauses.seconds)} collections, longest "
        f"{max(pauses.seconds, default=0) * 1e3:.1f} ms, "
        f"{len(compiles.seconds)} compiles while serving, longest call "
        f"{longest.t1 - longest.t0:.3f} s ({longest.rows} rows, at "
        f"{longest.t0 - w0:.1f} s into the window)")
    out.update({"correct": passes(checks), "attempted": int(len(due)),
                "failed": int(failed), "metrics": metrics, "device": device,
                "checks": checks})
    return {k: out[k] for k in ("correct", "attempted", "failed", "metrics",
                                "device", "breakdown", "checks") if k in out}


def check_sample(config: Dict[str, Any], seed: int, arrival: np.ndarray,
                 w0: float, w1: float) -> np.ndarray:
    """The requests due in [w0, w1) whose answers the check compares,
    drawn from the seed before serving, so the timed path can keep
    their logits."""
    due = np.flatnonzero((arrival >= w0) & (arrival < w1))
    k = min(int(config["correct"]["sample_requests"]), due.size)
    return np.sort(gen.rng(seed, gen.SAMPLE_STREAM).choice(
        due, k, replace=False)) if k else np.zeros(0, np.int64)


def judge(config: Dict[str, Any], ref: Optional[np.ndarray],
          tokens: np.ndarray, logits: np.ndarray, unanswered: int
          ) -> Dict[str, Dict[str, float]]:
    """Each number compared, beside its limit from the file.

    ``ref`` holds the reference's logits (N, G, V) at the positions
    that predicted the served ``tokens`` (N, G); ``logits`` are what
    the timed path computed there. With no request to compare, both
    numbers are infinite.
    """
    lim = config["correct"]
    if ref is None or not tokens.size:
        gap = err = float("inf")
    else:
        gap = reference.widest_gap(ref, tokens)
        err = reference.widest_logit_error(ref, logits)
    return {"token_gap": {"value": gap, "limit": float(lim["max_token_gap"])},
            "logit_error": {"value": err,
                            "limit": float(lim["max_logit_error"])},
            "unanswered": {"value": int(unanswered), "limit": 0}}


def passes(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def print_checks(checks: Dict[str, Dict[str, float]]) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
