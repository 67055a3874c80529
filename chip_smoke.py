#!/usr/bin/env python3
"""Smoke run of InferLine's main path on a TPU, end to end, in one process.

    python chip_smoke.py             # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4   # four replicas, one per chip: (a), (e)

Phases, each printing its own lines:

  (a) device   platform, device kind and count; fails unless JAX sees a
               TPU whose kind the hardware menu knows.
  (b) kernels  flash causal, decode, rmsnorm (incl. a ragged row count)
               and mamba_scan at real widths against kernels/ref.py on
               the chip; fails past a bf16-scale tolerance.
  (c) serve    full-width llama3.2-1b (random weights, the registered
               dtypes) behind the StageRuntime: profiled on the chip,
               planned by the Planner, ~32 Poisson requests served by
               PipelineExecutor; every answer checked, compiles inside
               the serving window counted, Pallas kernels present in the
               compiled stage, and 2 generations checked against a
               teacher-forced full forward pass.
  (d) planner  Planner.plan on the device grid path (backend="jax")
               against numpy: identical plans; prints the largest
               percentile gap.
  (e) replicas (--chips 4 only) one stage, replicas=4, one per device,
               compared with a one-replica run on device 0.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; it is printed
only when every phase passed. Without a TPU the script exits 1.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.configs.pipelines import arch_model_spec  # noqa: E402
from repro.core.hardware import hardware_for_device  # noqa: E402
from repro.core.pipeline import (  # noqa: E402
    PipelineConfig, StageConfig, linear_pipeline)
from repro.core.planner import Planner  # noqa: E402
from repro.core.profiler import (  # noqa: E402
    ProfileStore, profile_model_analytic, profile_model_measured)
from repro.kernels import ref  # noqa: E402
from repro.kernels.decode_attention import decode_attention  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.mamba_scan import mamba_scan  # noqa: E402
from repro.kernels.rmsnorm import rmsnorm  # noqa: E402
from repro.serving.executor import PipelineExecutor  # noqa: E402
from repro.serving.runtime import (  # noqa: E402
    GEN_TOKENS, StageRuntime, enable_compile_cache)
from repro.sim import SimEngine  # noqa: E402

MODEL = "llama3.2-1b"
SEQ_LEN = 128          # prompt tokens per request
N_REQUESTS = 32
SEED = 0
TOL = 2e-2             # max |out - ref| / max(1, max |ref|): bf16 scale


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_err(out, want) -> float:
    out = np.asarray(out, np.float32)
    want = np.asarray(want, np.float32)
    check(bool(np.isfinite(out).all()), "non-finite output")
    return float(np.abs(out - want).max() / max(1.0, np.abs(want).max()))


def poisson(n: int, rate: float, rng) -> np.ndarray:
    return np.cumsum(rng.exponential(1.0 / rate, n))


class CompileCounter:
    """Counts XLA compiles (and compile-cache loads) while active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0

    def _on_event(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_event)


# -- (a) -------------------------------------------------------------------

def phase_device(chips: int):
    devs = jax.devices()
    d0 = devs[0]
    print(f"[a device] platform={d0.platform} kind={d0.device_kind!r} "
          f"count={len(devs)}")
    check(d0.platform == "tpu", f"no TPU: JAX runs on {d0.platform!r}")
    hw = hardware_for_device(d0)
    check(len(devs) >= chips, f"--chips {chips} but {len(devs)} devices")
    print(f"[a device] hardware menu entry {hw}")
    return devs[:chips], hw


# -- (b) -------------------------------------------------------------------

def phase_kernels() -> None:
    cfg = get_arch(MODEL)
    h, kv, hd, d = (cfg.num_heads, cfg.num_kv_heads,
                    cfg.resolved_head_dim, cfg.d_model)
    ks = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))

    def normal(shape, scale=1.0):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    q, k, v = normal((1, 2048, h, hd)), normal((1, 2048, kv, hd)), \
        normal((1, 2048, kv, hd))
    # decode reads layer 1 of a head-major (L, B, KV, Smax, hd) stack
    qd, kd, vd = normal((8, 1, h, hd)), normal((2, 8, kv, 2048, hd)), \
        normal((2, 8, kv, 2048, hd))
    d_in, n = 16384, 16          # jamba-1.5-large mamba width, d_state
    dt = jax.nn.softplus(normal((1, 512, d_in), 0.3))
    xm, bm, cm = normal((1, 512, d_in)), normal((1, 512, n), 0.5), \
        normal((1, 512, n), 0.5)
    a = -jnp.exp(normal((d_in, n), 0.3))
    h0 = normal((1, d_in, n), 0.1)
    scale = normal((d,))
    x1, x2 = normal((8, 128, d)), normal((3, 100, d))
    cases = {
        "flash_causal": (lambda: flash_attention(q, k, v, causal=True),
                         lambda: ref.flash_attention_ref(q, k, v)),
        "decode": (lambda: decode_attention(qd, kd, vd, 1500, 1),
                   lambda: ref.decode_attention_ref(qd, kd, vd, 1500, 1)),
        "rmsnorm_8x128": (lambda: rmsnorm(x1, scale),
                          lambda: ref.rmsnorm_ref(x1, scale)),
        "rmsnorm_3x100": (lambda: rmsnorm(x2, scale),
                          lambda: ref.rmsnorm_ref(x2, scale)),
        "mamba_scan": (lambda: mamba_scan(dt, xm, bm, cm, a, h0),
                       lambda: ref.mamba_scan_ref(dt, xm, bm, cm, a, h0)),
    }
    for name, (kernel, oracle) in cases.items():
        out = kernel()
        with jax.default_matmul_precision("highest"):
            want = oracle()
        err = max(rel_err(o, w) for o, w in zip(
            jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(want)))
        print(f"[b kernels] {name:14s} max_rel_err={err:.3e} tol={TOL}")
        check(err <= TOL, f"kernel {name} error {err:.3e} > {TOL}")


# -- (c) -------------------------------------------------------------------

def phase_serve(dev, hw: str) -> None:
    cfg = get_arch(MODEL)
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    rt = StageRuntime(cfg, [dev], seq_len=SEQ_LEN, max_batch=16)
    print(f"[c serve] {MODEL} d_model={cfg.d_model} layers="
          f"{cfg.num_layers} params={cfg.param_dtype} "
          f"compute={cfg.compute_dtype}: init + compile of buckets "
          f"{rt.buckets} took {time.perf_counter() - t0:.1f}s")
    n_kernels = rt.compiled(1).as_text().count("tpu_custom_call")
    print(f"[c serve] compiled stage holds {n_kernels} tpu_custom_call")
    check(n_kernels > 0, "compiled stage has no Pallas kernel")

    prof = profile_model_measured(MODEL, rt.profile_batch, hw,
                                  batch_sizes=rt.buckets)
    store = ProfileStore()
    store.add(prof)
    print("[c serve] measured batch latency (ms): " + ", ".join(
        f"b={b}: {prof.batch_latency(hw, b) * 1e3:.2f}"
        for b in rt.buckets))
    pipe = linear_pipeline("llama-generate", [MODEL], {MODEL: [hw]})
    (stage,) = pipe.stages
    rate = 0.5 * prof.max_throughput(hw)
    slo = 4.0 * prof.batch_latency(hw, rt.max_batch)
    plan = Planner(pipe, store).plan(poisson(2000, rate, rng), slo)
    check(plan.feasible, "planner found no feasible plan")
    planned = plan.config[stage]
    served = dataclasses.replace(
        planned, batch_size=min(planned.batch_size, rt.max_batch))
    config = PipelineConfig({stage: served})
    print(f"[c serve] plan at {rate:.1f} qps, slo {slo * 1e3:.1f} ms: "
          f"{planned.hardware} batch {planned.batch_size} (served "
          f"{served.batch_size}) x {planned.replicas} replicas, "
          f"${plan.cost_per_hr:.2f}/hr")

    prompts = rng.integers(0, cfg.vocab_size, (N_REQUESTS, SEQ_LEN),
                           dtype=np.int32)
    ex = PipelineExecutor(pipe, config, {MODEL: rt}, solo_latency_s={
        stage: prof.batch_latency(hw, 1)})
    answers = {}
    ex.on_request_done = lambda req: answers.__setitem__(req.rid,
                                                         req.payload)
    try:
        with CompileCounter() as compiles:
            lat = ex.serve_trace(poisson(N_REQUESTS, rate, rng),
                                 lambda i: prompts[i], timeout_s=120.0)
    finally:
        ex.shutdown()
    print(f"[c serve] {N_REQUESTS} requests: p50={np.median(lat) * 1e3:.1f}"
          f" ms max={lat.max() * 1e3:.1f} ms, mean batch "
          f"{ex.batch_sizes()[stage].mean():.2f}, compiles in the serving "
          f"window: {compiles.n}")
    check(bool(np.isfinite(lat).all()), "a request was not answered")
    for i in range(N_REQUESTS):
        out = answers.get(i)
        check(isinstance(out, np.ndarray) and out.shape == (GEN_TOKENS,)
              and out.dtype == np.int32
              and bool(((out >= 0) & (out < cfg.vocab_size)).all()),
              f"request {i}: malformed answer {out!r}")
    check(compiles.n == 0, f"{compiles.n} compiles while serving")

    gen, logits = rt.generate(prompts[:2])
    want = rt.teacher_forced(prompts[:2], np.asarray(gen))
    err = rel_err(logits, want)
    same = [bool(np.array_equal(np.asarray(gen)[j], answers[j]))
            for j in range(2)]
    print(f"[c serve] cached generation vs teacher-forced forward: "
          f"max_rel_err={err:.3e} tol={TOL}; served tokens equal "
          f"direct ones: {same}")
    check(err <= TOL, f"generation error {err:.3e} > {TOL}")


# -- (d) -------------------------------------------------------------------

def phase_planner() -> None:
    spec = arch_model_spec(MODEL, seq_in=SEQ_LEN)
    store = ProfileStore()
    store.add(profile_model_analytic(spec, batch_sizes=tuple(range(1, 33))))
    pipe = linear_pipeline("llama-plan", [MODEL])
    arrivals = poisson(6000, 1500.0, np.random.default_rng(SEED))
    plans, grids = {}, 0
    for backend in ("numpy", "jax"):
        planner = Planner(pipe, store, backend=backend)
        t0 = time.perf_counter()
        plans[backend] = planner.plan(arrivals, 0.25)
        grids = planner.session_stats.get("device_grids", 0)
        print(f"[d planner] {backend:5s} plan {plans[backend].config.cache_key()}"
              f" ${plans[backend].cost_per_hr:.2f}/hr in "
              f"{time.perf_counter() - t0:.2f}s, device grids {grids}")
    check(grids > 0, "the device grid path did not run")
    a, b = plans["numpy"], plans["jax"]
    check(a.feasible and b.feasible, "infeasible plan")
    check(a.config.cache_key() == b.config.cache_key()
          and a.cost_per_hr == b.cost_per_hr, "plans differ")

    (stage,) = pipe.stages
    grid = [PipelineConfig({stage: StageConfig(hw, bs, r)})
            for hw in ("tpu-v5e-1", "tpu-v5e-4")
            for bs in (4, 8, 16, 32, 64, 96) for r in range(1, 9)]
    engine = SimEngine(pipe, store)
    host = np.asarray(engine.session(arrivals).percentile_many(grid, 99.0))
    session = engine.session(arrivals, backend="jax")
    dev = np.asarray(session.percentile_many(grid, 99.0))
    check(session.stats["device_grids"] > 0, "grid not scored on device")
    fin = np.isfinite(host)
    check(bool((fin == np.isfinite(dev)).all()), "finiteness differs")
    gap = float(np.abs(host[fin] - dev[fin]).max()) if fin.any() else 0.0
    print(f"[d planner] {len(grid)}-candidate p99 grid: largest gap "
          f"{gap:.3e} s, bit-identical: {bool((host == dev).all())}")


# -- (e) -------------------------------------------------------------------

def phase_replicas(devs, hw: str) -> None:
    cfg = get_arch(MODEL)
    t0 = time.perf_counter()
    rt = StageRuntime(cfg, devs, seq_len=SEQ_LEN, max_batch=4)
    print(f"[e replicas] runtime on {len(devs)} devices, buckets "
          f"{rt.buckets}: {time.perf_counter() - t0:.1f}s")
    pipe = linear_pipeline("llama-replicas", [MODEL], {MODEL: [hw]})
    (stage,) = pipe.stages
    prompts = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (N_REQUESTS, SEQ_LEN), dtype=np.int32)

    def serve(replicas: int):
        before = list(rt.batches)
        ex = PipelineExecutor(pipe, PipelineConfig(
            {stage: StageConfig(hw, rt.max_batch, replicas)}), {MODEL: rt})
        answers = {}
        ex.on_request_done = lambda req: answers.__setitem__(req.rid,
                                                             req.payload)
        try:
            lat = ex.serve_trace(np.zeros(N_REQUESTS),
                                 lambda i: prompts[i], timeout_s=120.0)
        finally:
            ex.shutdown()
        check(bool(np.isfinite(lat).all()) and len(answers) == N_REQUESTS,
              f"replicas={replicas}: a request was not answered")
        return answers, [a - b for a, b in zip(rt.batches, before)]

    # the one-replica run goes first: its thread is the first the runtime
    # binds, so it lands on device 0; the four that follow take 1, 2, 3, 0
    one, counts1 = serve(1)
    four, counts4 = serve(len(devs))
    print(f"[e replicas] batches per device: 1 replica {counts1}, "
          f"{len(devs)} replicas {counts4}")
    check(counts1[0] > 0 and sum(counts1[1:]) == 0,
          "the one-replica run did not run on device 0 alone")
    check(min(counts4) > 0, "a device served no batch")
    diff = [i for i in range(N_REQUESTS)
            if not np.array_equal(one[i], four[i])]
    print(f"[e replicas] {N_REQUESTS - len(diff)}/{N_REQUESTS} answers "
          f"equal to the one-replica run")
    check(not diff, f"answers differ for requests {diff}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    cache = enable_compile_cache()
    try:
        devs, hw = phase_device(args.chips)
        print(f"[a device] compile cache {cache}")
        if args.chips == 1:
            phase_kernels()
            phase_serve(devs[0], hw)
            phase_planner()
        else:
            phase_replicas(devs, hw)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    d0 = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
