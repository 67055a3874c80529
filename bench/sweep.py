#!/usr/bin/env python3
"""Find a configuration's knee once, on the chip: the highest Poisson
rate it sustains as its file fixes it.

    python bench/sweep.py --config phi3-mini-3.8b --seed 5 \\
        --fractions 0.5,0.7,0.8,0.9,1.0,1.1 --seconds 10

It times every bucket (best of three), takes the largest bucket's rate
``max_batch / t(max_batch)`` as the capacity estimate, then serves each
fraction of it open loop through the executor and prints one JSON line
per rate: offered and completed rates, p50, p95 and the ratio of the
mean latency of the last quarter of arrivals to the first (a backlog
that grows through the run reads well above 1). The mixes' absolute
rates come from these lines and are recorded in PERF.md; a run of the
benchmark never sweeps.

``--trace-sample DIR`` also records a small trace of two stage calls
into DIR and prints what its planes, lines and operations are named.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def summarize_trace(path: str) -> None:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            names = {}
            for e in line.events:
                names[e.name] = names.get(e.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:25]
            lines.append({"line": line.name, "events": sum(names.values()),
                          "top": top})
        print(json.dumps({"plane": plane.name, "lines": lines}))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--fractions", default="0.5,0.7,0.8,0.9,1.0,1.1")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--trace-sample", default="")
    args = ap.parse_args()

    import jax
    import numpy as np
    from bench import arrivals as gen, harness, spec, stats, trace as tr
    from bench.serve import StageRecorder
    from bench.weights import base_key
    from repro.serving.runtime import enable_compile_cache

    devices = harness.find_devices(args.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = spec.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == args.config)
    cfg = spec.load_json(spec.ROOT / entry["file"])
    _, rt = harness.build_runtime(cfg, spec.family(cfg["family"]),
                                  base_key(args.seed), devices)
    print(json.dumps({"setup_s": time.perf_counter() - T_START,
                      "memory_peak_bytes": max(
                          (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                          for d in devices)}), flush=True)
    times = {}
    for b in rt.buckets:
        toks = np.zeros((b, rt.seq_len), np.int32)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(rt.generate(toks))
            best = min(best, time.perf_counter() - t0)
        times[b] = best
    cap = rt.max_batch / times[rt.max_batch] * len(devices)
    print(json.dumps({"bucket_ms": {b: t * 1e3 for b, t in times.items()},
                      "capacity_rps": cap}), flush=True)

    vocab = int(cfg["vocab_size"])
    if args.trace_sample:
        out = Path(args.trace_sample)
        shutil.rmtree(out, ignore_errors=True)
        prompts = list(np.zeros((2, rt.seq_len), np.int32))
        rec = StageRecorder(rt, rt, {id(p): i for i, p in enumerate(prompts)})
        jax.profiler.start_trace(str(out),
                                 profiler_options=harness.profile_options())
        rec([prompts[0]])
        rec(prompts)
        jax.profiler.stop_trace()
        path = tr.find_xplane(str(out))
        print(json.dumps({"trace_sample": path,
                          "bytes": Path(path).stat().st_size,
                          "calls": rec.calls}), flush=True)
        summarize_trace(path)

    for frac in map(float, args.fractions.split(",")):
        rate = frac * cap
        traffic = {"kind": "poisson", "rate_rps": rate}
        arr = gen.arrivals(traffic, gen.rng(args.seed, 0), 0.0, args.seconds)
        prompts = list(gen.rng(args.seed, 1).integers(
            0, vocab, (arr.size, rt.seq_len), dtype=np.int32))
        ex = harness.make_executor(args.config, cfg, rt, devices, rt)
        try:
            lat = ex.serve_trace(arr, lambda i: prompts[i], timeout_s=120.0)
        finally:
            ex.shutdown()
        done = arr + lat
        q = arr.size // 4
        lat_ms = lat * 1e3
        print(json.dumps({
            "fraction": frac, "offered_rps": rate,
            "completed_rps": arr.size / (done.max() - arr.min()),
            "p50_ms": stats.percentile(lat_ms, 50),
            "p95_ms": stats.percentile(lat_ms, 95),
            "growth": float(lat[-q:].mean() / lat[:q].mean()),
            "mean_batch": float(np.mean(ex.batch_sizes()[
                next(iter(ex.pipeline.stages))]))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
