"""The span facility: nothing is built or recorded while no profiler
trace records, and a recording trace holds each span with its args."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np

from repro.core.pipeline import PipelineConfig, StageConfig, linear_pipeline
from repro.serving import spans
from repro.serving.executor import PipelineExecutor

SRC = Path(__file__).resolve().parents[1] / "src"


class _Unprintable:
    def __str__(self):
        raise AssertionError("an untraced span formatted its args")

    __repr__ = __str__


def test_untraced_span_is_the_shared_null_context():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    s = spans.span("runtime.pad", bucket=_Unprintable())
    assert s is spans.NULL
    with s as entered:
        assert entered is None


def test_untraced_executor_makes_no_annotation(monkeypatch):
    made = []

    class Counting:
        @staticmethod
        def is_enabled():
            return False

        def __init__(self, *a, **k):
            made.append(a)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    pipe = linear_pipeline("p", ["m"], {"m": ["cpu-1"]})
    (stage,) = pipe.stages
    ex = PipelineExecutor(pipe, PipelineConfig(
        {stage: StageConfig("cpu-1", 4, 2)}), {"m": lambda xs: xs})
    try:
        lat = ex.serve_trace(np.linspace(0.0, 0.05, 10), lambda i: i,
                             timeout_s=30.0)
    finally:
        ex.shutdown()
    assert np.isfinite(lat).all()
    assert made == []


def test_executor_does_not_import_jax():
    # JAX's objects would lengthen every full collection of a process
    # that serves CPU stages only
    code = ("import sys, repro.serving.executor, repro.serving.ingress; "
            "sys.exit('jax' in sys.modules)")
    env = {**os.environ, "PYTHONPATH": str(SRC)}
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=120).returncode == 0


def test_traced_span_carries_its_args(tmp_path):
    jax.profiler.start_trace(str(tmp_path), profiler_options=_host_spans_only())
    try:
        with spans.span("executor.form") as form:
            assert form is not None
            form.set_metadata(rows=3, wait_ms=1.5)
        with spans.span("executor.inject", rid=7, lag_us=12.5):
            pass
    finally:
        jax.profiler.stop_trace()
    assert spans.span("x") is spans.NULL
    (path,) = tmp_path.rglob("*.xplane.pb")
    got = {e.name: dict(e.stats)
           for p in jax.profiler.ProfileData.from_file(str(path)).planes
           if p.name.startswith("/host:") for line in p.lines
           for e in line.events if e.name.startswith("executor.")}
    assert got == {"executor.form": {"rows": 3, "wait_ms": 1.5},
                   "executor.inject": {"rid": 7, "lag_us": 12.5}}


def _host_spans_only():
    """Profiler options that record TraceMe spans and no Python calls."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts
