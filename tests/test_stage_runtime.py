"""StageRuntime on the CPU at smoke size: generation matches a
teacher-forced forward pass, the executor answers every request through
it, replicas bind to devices round robin, and ``chip_smoke.py`` refuses
to run without a TPU."""

import os
import subprocess
import sys
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.pipeline import PipelineConfig, StageConfig, linear_pipeline
from repro.serving import runtime as rt_mod
from repro.serving.executor import PipelineExecutor
from repro.serving.runtime import GEN_TOKENS, StageRuntime

REPO = Path(__file__).resolve().parents[1]
MODEL = "llama3.2-1b"
SEQ = 16


@pytest.fixture(scope="module")
def rt():
    return StageRuntime(get_smoke(MODEL), jax.devices()[:1], seq_len=SEQ,
                        max_batch=4)


def _prompts(rt, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, rt.cfg.vocab_size, (n, SEQ), dtype=np.int32)


def test_generate_matches_teacher_forced_forward(rt):
    toks = _prompts(rt, 2)
    gen, logits = rt.generate(toks)
    gen, logits = np.asarray(gen), np.asarray(logits)
    assert gen.shape == (2, GEN_TOKENS)
    assert logits.shape == (2, GEN_TOKENS, rt.cfg.vocab_size)
    want = np.asarray(rt.teacher_forced(toks, gen))
    np.testing.assert_allclose(logits, want, atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(gen, want.argmax(-1))


def test_executor_answers_every_request_through_runtime(rt):
    pipe = linear_pipeline("gen", [MODEL], {MODEL: ["cpu-1"]})
    (stage,) = pipe.stages
    prompts = _prompts(rt, 12, seed=1)
    ex = PipelineExecutor(pipe, PipelineConfig(
        {stage: StageConfig("cpu-1", 4, 2)}), {MODEL: rt})
    answers = {}
    ex.on_request_done = lambda req: answers.__setitem__(req.rid,
                                                         req.payload)
    before = sum(rt.batches)
    try:
        lat = ex.serve_trace(np.linspace(0.0, 0.2, 12), lambda i: prompts[i],
                             timeout_s=60.0)
    finally:
        ex.shutdown()
    assert np.isfinite(lat).all()
    assert sorted(answers) == list(range(12))
    direct = np.asarray(rt.generate(prompts[:4])[0])
    for i in range(4):
        np.testing.assert_array_equal(answers[i], direct[i])
    assert sum(rt.batches) - before == len(ex.batch_sizes()[stage])


def test_oversize_batch_and_untiled_prompt_are_refused(rt):
    with pytest.raises(ValueError, match="bucket"):
        rt(list(_prompts(rt, 5)))
    with pytest.raises(ValueError, match="tile"):
        StageRuntime(get_smoke(MODEL), jax.devices()[:1], seq_len=200,
                     max_batch=1)


@pytest.mark.parametrize("n,flash,cache", [
    (100, 100, 100), (136, 256, 136), (600, 640, 1024)])
def test_lengths_respect_kernel_tiling(n, flash, cache):
    assert rt_mod._flash_len(n) == flash
    assert rt_mod._cache_len(n) == cache


def test_replica_threads_bind_devices_round_robin():
    cpu = jax.devices()[0]
    two = StageRuntime(get_smoke(MODEL), [cpu, cpu], seq_len=SEQ,
                       max_batch=1)
    prompt = _prompts(two, 1)
    for _ in range(2):
        t = threading.Thread(target=two, args=([prompt[0]],))
        t.start()
        t.join(60.0)
        assert not t.is_alive()
    assert two.batches == [1, 1]


def test_compile_cache_dir(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    was = jax.config.jax_compilation_cache_dir
    assert rt_mod.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = rt_mod.enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_chip_smoke_fails_without_tpu():
    r = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def _host_spans(logdir):
    """(name, start_ns, end_ns, thread line, args) of every executor and
    runtime span in the trace under `logdir`."""
    (path,) = Path(logdir).rglob("*.xplane.pb")
    out = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("executor.", "runtime.")):
                    out.append((e.name, int(e.start_ns), int(e.end_ns), li,
                                dict(e.stats)))
    return out


def test_traced_executor_names_every_layer(rt, tmp_path):
    pipe = linear_pipeline("gen", [MODEL], {MODEL: ["cpu-1"]})
    (stage,) = pipe.stages
    prompts = _prompts(rt, 8, seed=2)
    # tracing starts first: a replica opens its first formation span
    # as soon as it starts
    jax.profiler.start_trace(str(tmp_path), profiler_options=_host_spans_only())
    ex = PipelineExecutor(pipe, PipelineConfig(
        {stage: StageConfig("cpu-1", 4, 2)}), {MODEL: rt})
    try:
        lat = ex.serve_trace(np.linspace(0.0, 0.1, 8), lambda i: prompts[i],
                             timeout_s=60.0)
    finally:
        jax.profiler.stop_trace()
        ex.shutdown()
    assert np.isfinite(lat).all()
    spans = _host_spans(tmp_path)
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    n_batch = len(ex.batch_sizes()[stage])
    assert sorted(s[4]["rid"] for s in by["executor.inject"]) == list(
        range(8))
    assert all(s[4]["lag_us"] >= 0 for s in by["executor.inject"])
    batches = by["executor.batch"]
    assert len(batches) == n_batch
    for name in ("executor.complete", "runtime.pad", "runtime.put",
                 "runtime.launch", "runtime.fetch"):
        assert len(by[name]) == n_batch, name
    # every batch's inner spans sit inside it, on its replica thread
    for b in batches:
        inner = [s for s in spans if s[3] == b[3] and b[1] <= s[1]
                 and s[2] <= b[2] and s is not b]
        assert [s[0] for s in sorted(inner, key=lambda s: s[1])] == [
            "runtime.pad", "runtime.put", "runtime.launch", "runtime.fetch",
            "executor.complete"]
        args = {s[0]: s[4] for s in inner}
        assert args["runtime.pad"]["rows"] == b[4]["rows"]
        assert args["runtime.pad"]["bucket"] >= b[4]["rows"]
        assert args["runtime.launch"] == {"device": 0}
        assert b[4]["stage"] == stage and 0 <= b[4]["rid0"] < 8
    # formation spans are on the replica threads, outside any batch
    replica_lines = {b[3] for b in batches}
    inject_lines = {s[3] for s in by["executor.inject"]}
    assert not replica_lines & inject_lines
    formed = [s for s in by["executor.form"] if "rows" in s[4]]
    assert len(formed) == n_batch
    assert {s[3] for s in formed} <= replica_lines
    assert all(s[4]["wait_ms"] >= 0 for s in formed)
    assert not any(b[3] == f[3] and b[1] < f[1] < b[2]
                   for b in batches for f in formed)
    # the batch's executor-clock start `t` maps onto the trace clock by
    # one offset, as the harness's own spans do
    offsets = [b[1] - b[4]["t"] * 1e9 for b in batches]
    assert max(offsets) - min(offsets) < 50e6


def test_program_phases_are_named_scopes(rt):
    hlo = rt.compiled(1).as_text()
    assert "/prefill/" in hlo and "/decode/" in hlo


def _host_spans_only():
    """Profiler options that record TraceMe spans and no Python calls."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts
