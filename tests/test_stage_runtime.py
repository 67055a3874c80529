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
