#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python bench/control.py --workload phi3-overload --seeds 1,2,3 --seconds 4

For each seed, in one process: weights from the seed, a short window of
the cell's own traffic served through the same runtime, recorder and
executor as a benchmark run, and then, over the same sample of requests
a run checks, the reference and the controls. A control is the family's
plain reference computed a step lower (each of its ``MODES`` after the
first; for the dense family ``bfloat16``, the step below the float32
the configuration states, and ``int8``, the step below that), put in
the program's place: its logits at the same prompts and served
tokens, and at each position the token it puts first. Each goes through
the harness's own comparison (:func:`bench.harness.judge`), so each
line gives the numbers compared and ``correct`` for the program and
for every control.

The limits in ``bench/configs/<config>.json`` lie between the largest
program reading and the smallest control reading; PERF.md keeps them.
The benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, seed: int, seconds: float, devices, registry=None):
    """The numbers compared, and ``correct``, for the program and each
    control on one seed."""
    import jax
    import numpy as np
    from bench import arrivals as gen, harness, reference
    from bench.serve import StageRecorder
    from bench.weights import base_key
    from repro.serving.runtime import GEN_TOKENS

    cfg, traffic, family = cell.config, cell.traffic, cell.family
    key = base_key(seed)
    kw = {} if registry is None else {"registry": registry}
    _, rt = harness.build_runtime(cfg, family, key, devices, **kw)
    arrival = gen.run_arrivals(traffic, seed, seconds)
    prompts = gen.rng(seed, gen.PROMPTS_STREAM).integers(
        0, int(cfg["vocab_size"]), (arrival.size, rt.seq_len), dtype=np.int32)
    plist = list(prompts)
    w0 = float(traffic["warmup_s"])
    sample = harness.check_sample(cfg, seed, arrival, w0, w0 + seconds)
    rec = StageRecorder(rt, rt, {id(p): i for i, p in enumerate(plist)},
                        keep=sample)
    ex = harness.make_executor(cell.name, cfg, rt, devices, rec)
    answers = [None] * arrival.size
    ex.on_request_done = lambda req: answers.__setitem__(req.rid, req.payload)
    try:
        ex.serve_trace(arrival, lambda i: plist[i],
                       timeout_s=float(traffic["drain_s"]))
    finally:
        ex.shutdown()
    logits = rec.kept_logits(sample, (GEN_TOKENS, int(cfg["vocab_size"])))
    rec.fn = rec.runtime = None
    rec.kept.clear()
    del ex, rt, rec
    gc.collect()
    served = np.stack([answers[i] for i in sample])
    seqs = reference.teacher_forced(prompts[sample], served)
    out = {"seed": seed, "requests": int(sample.size),
           "tokens": int(served.size)}
    with jax.default_device(devices[0]):
        ref = family.logits(cfg, key, seqs, GEN_TOKENS)
        reads = {"program": (served, logits)}
        for mode in family.MODES[1:]:
            lg = family.logits(cfg, key, seqs, GEN_TOKENS, mode=mode)
            reads[mode] = (lg.argmax(-1), lg)
    for name, (tokens, lg) in reads.items():
        checks = harness.judge(cfg, ref, tokens, lg, 0)
        for c, v in checks.items():
            if c != "unanswered":
                out[f"{name}.{c}"] = v["value"]
        out[f"{name}.correct"] = harness.passes(checks)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args()

    import jax
    from bench import harness, spec
    from repro.serving.runtime import enable_compile_cache

    cell = spec.load_cell(args.workload)
    devices = harness.find_devices(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for seed in map(int, args.seeds.split(",")):
        t0 = time.perf_counter()
        r = readings(cell, seed, args.seconds, devices)
        r["seconds"] = time.perf_counter() - t0
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
