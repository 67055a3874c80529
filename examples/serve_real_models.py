"""End-to-end driver: serve REAL JAX models with a closed-loop Tuner.

The paper's kind is a serving system, so the end-to-end example deploys
actual jitted models (reduced variants of two assigned architectures) on
the default JAX device with the real thread-pool executor. Each stage is
a :class:`repro.serving.runtime.StageRuntime` that greedily generates
``GEN_TOKENS`` per request; the second stage continues from the first
stage's tokens.

  1. measured-profile both models under the hardware-menu entry of the
     device they run on,
  2. plan the two-stage cascade with the Planner against the profile,
  3. deploy the planned config to PipelineExecutor (real centralized
     policy-aware batched queues + replica threads),
  4. serve a Poisson trace of batched requests and report latency vs the
     Estimator's prediction (Fig. 8 fidelity),
  5. close the loop: a traffic spike hits the running pipeline and the
     ClosedLoopTuner — the same controller used in co-simulation —
     scales the real replica fleet through the LiveControlLoop.

Run:  PYTHONPATH=src python examples/serve_real_models.py
"""

import dataclasses

import jax
import numpy as np

from repro.configs import get_smoke
from repro.core.estimator import Estimator
from repro.core.hardware import hardware_for_device
from repro.core.pipeline import linear_pipeline
from repro.core.planner import Planner
from repro.core.profiler import ProfileStore, profile_model_measured
from repro.core.tuner import ClosedLoopTuner, TunerPlanInfo
from repro.serving.executor import PipelineExecutor
from repro.serving.loop import LiveControlLoop
from repro.serving.runtime import (
    GEN_TOKENS, StageRuntime, enable_compile_cache)
from repro.workload.generator import gamma_trace

SEQ = 32            # prompt tokens into the first stage
MAX_BATCH = 16
SLO = 0.25          # 250 ms end-to-end on a CPU host
LAMBDA = 30.0       # queries/s


def main() -> None:
    enable_compile_cache()
    hw = hardware_for_device(jax.devices()[0])
    print(f"building + warming models on {hw} "
          f"(xlstm-125m-smoke -> llama3.2-1b-smoke cascade)")
    runtimes = {
        "stage_a": StageRuntime(get_smoke("xlstm-125m"), jax.devices()[:1],
                                seq_len=SEQ, max_batch=MAX_BATCH),
        "stage_b": StageRuntime(get_smoke("llama3.2-1b"), jax.devices()[:1],
                                seq_len=GEN_TOKENS, max_batch=MAX_BATCH),
    }

    print("profiling (measured wall-clock backend) ...")
    store = ProfileStore()
    for mid, rt in runtimes.items():
        store.add(profile_model_measured(mid, rt.profile_batch, hw,
                                         batch_sizes=rt.buckets))
        p = store.get(mid)
        print(f"  {mid}: lat(b=1)={p.batch_latency(hw, 1)*1e3:.1f}ms "
              f"lat(b=8)={p.batch_latency(hw, 8)*1e3:.1f}ms "
              f"max_thru={p.max_throughput(hw):.1f} qps")

    pipe = linear_pipeline("cascade", ["stage_a", "stage_b"],
                           {"stage_a": [hw], "stage_b": [hw]})
    sample = gamma_trace(LAMBDA, 1.0, 20, seed=0)
    plan = Planner(pipe, store).plan(sample, SLO)
    print("\nplanned configuration:")
    print(plan.describe())
    if not plan.feasible:
        raise SystemExit("infeasible on this host; lower LAMBDA")
    # the planner may extrapolate batch sizes past the profiled buckets;
    # serve at most the largest warmed one
    config = plan.config.copy()
    for s_name, cfg in config.stage_configs.items():
        config.stage_configs[s_name] = dataclasses.replace(
            cfg, batch_size=min(cfg.batch_size, MAX_BATCH))

    print("deploying to the real executor and serving 15 s of traffic...")
    solo = {s: store.get(pipe.stages[s].model_id).batch_latency(hw, 1)
            for s in pipe.stages}
    ex = PipelineExecutor(pipe, config, runtimes, solo_latency_s=solo)
    live = gamma_trace(LAMBDA, 1.0, 15, seed=1)
    prompts = np.random.default_rng(0).integers(
        0, 512, (live.size, SEQ), dtype=np.int32)
    payload = lambda i: prompts[i % len(prompts)]  # noqa: E731
    lat = ex.serve_trace(live, payload, slo_s=SLO)

    est = Estimator(pipe, store)
    predicted = est.simulate(config, live)
    print(f"\nserved {lat.size} queries:")
    print(f"  measured  p50={np.percentile(lat, 50)*1e3:7.1f}ms  "
          f"p99={np.percentile(lat, 99)*1e3:7.1f}ms  "
          f"miss={float((lat > SLO).mean()):.4f}")
    print(f"  estimator p50={predicted.percentile(50)*1e3:7.1f}ms  "
          f"p99={predicted.p99*1e3:7.1f}ms (Fig. 8 fidelity check)")
    mean_batch = {k: round(float(b.mean()), 1) if b.size else 0.0
                  for k, b in ex.batch_sizes().items()}
    print(f"  mean batch sizes: {mean_batch}")

    # ---- close the loop on the running pipeline -------------------------
    # the ClosedLoopTuner drives REAL threads through the same
    # step(EpochTelemetry) interface it uses in co-simulation; a 3x
    # traffic spike should scale the fleet up, then drain it back down
    print("\nclosed loop: 3x spike against the live executor ...")
    service = est.service_time(config)
    info = TunerPlanInfo.from_plan(pipe, config, store,
                                   gamma_trace(LAMBDA, 1.0, 60, seed=2),
                                   service)
    tuner = ClosedLoopTuner(info, max_replicas=4)
    loop = LiveControlLoop(ex, SLO, epoch_s=1.0, service_time_s=service)
    # the tail outlives DOWNSCALE_HYSTERESIS_S so the drain-and-retire
    # half of the lifecycle shows up too
    spike = np.concatenate([
        gamma_trace(LAMBDA, 1.0, 8, seed=3),
        8.0 + gamma_trace(3 * LAMBDA, 0.7, 5, seed=4),
        13.0 + gamma_trace(LAMBDA, 1.0, 17, seed=5)])
    run = loop.run(spike, tuner, payload)
    print(f"  served {run.latency.size} queries, "
          f"miss={run.miss_rate:.4f}, released={run.released}")
    for ev in run.events:
        print(f"  t={ev.t:5.1f}s  {ev.kind:6s} {ev.stage:16s} "
              f"value={ev.value:+.0f}")
    for stage, tl in run.replica_timeline.items():
        print(f"  {stage} replicas: " +
              " -> ".join(f"{c}@{t:.0f}s" for t, c in tl))
    ex.shutdown()


if __name__ == "__main__":
    main()
