"""JAX backend parity: device fills and grids vs the numpy reference.

The device backend's contract (repro.sim.jax_backend) is *bit identity*:
``lax.scan`` fills, device percentile reductions, and the vmapped
(hw, batch, replica) candidate grid must reproduce the numpy kernels to
the last ulp wherever IEEE-754 float64 semantics allow.  These tests
force the device paths (the auto-selection thresholds would otherwise
route small problems to numpy) and compare exactly — not approximately.

Plan-decision identity is the end-to-end bar: Planner and BeamPlanner
must return the same configuration at the same cost on both backends.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from tests._hyp import given, settings, st

from repro.configs.pipelines import get_motif
from repro.core.pipeline import PipelineConfig, StageConfig
from repro.core.planner import BeamPlanner, Planner
from repro.sim import SimEngine, simulate_stage
from repro.sim import jax_backend as jb

# -- helpers ----------------------------------------------------------------

def _both_fills(ready, lut, max_batch, replicas,
                replica_events=None, timeout_s=0.0):
    """Run one fifo fill on both backends, forcing the device kernel."""
    a = simulate_stage("fifo", ready, lut, max_batch, replicas,
                       replica_events, timeout_s)
    old = jb._JAX_FILL_THRESHOLD
    jb._JAX_FILL_THRESHOLD = 0
    try:
        b = simulate_stage("fifo", ready, lut, max_batch, replicas,
                           replica_events, timeout_s, backend="jax")
    finally:
        jb._JAX_FILL_THRESHOLD = old
    return a, b


def _assert_fill_equal(a, b):
    done_a, batches_a, dropped_a = a
    done_b, batches_b, dropped_b = b
    np.testing.assert_array_equal(done_a, done_b)
    np.testing.assert_array_equal(batches_a, batches_b)
    np.testing.assert_array_equal(dropped_a, dropped_b)


def _ready_from_gaps(gaps, rate_scale):
    # fixed-length traces keep the jitted scan's shape cache warm
    g = np.asarray(gaps, dtype=np.float64) * rate_scale
    return np.cumsum(g)


def _lut(max_batch, base, slope):
    lut = np.full(max_batch + 1, -1.0)
    for b in range(1, max_batch + 1):
        lut[b] = base + slope * b
    return lut


# -- fill parity (tentpole bit-identity) ------------------------------------

@settings(max_examples=12, deadline=None)
@given(
    gaps=st.lists(st.floats(min_value=0.0, max_value=0.05),
                  min_size=60, max_size=60),
    max_batch=st.integers(min_value=1, max_value=8),
    replicas=st.integers(min_value=1, max_value=4),
    regime=st.integers(min_value=0, max_value=2),
    timeout_i=st.integers(min_value=0, max_value=1),
)
def test_static_fill_bit_identical(gaps, max_batch, replicas, regime,
                                   timeout_i):
    # regimes: underload, ~critical, overload (service >> arrival gap)
    scale = (4.0, 1.0, 0.05)[regime]
    ready = _ready_from_gaps(gaps, scale)
    lut = _lut(max_batch, base=0.01, slope=0.004)
    timeout_s = (0.0, 0.03)[timeout_i]
    a, b = _both_fills(ready, lut, max_batch, replicas,
                       timeout_s=timeout_s)
    _assert_fill_equal(a, b)


@settings(max_examples=8, deadline=None)
@given(
    gaps=st.lists(st.floats(min_value=0.0, max_value=0.05),
                  min_size=60, max_size=60),
    replicas=st.integers(min_value=1, max_value=3),
)
def test_batch_one_fill_bit_identical(gaps, replicas):
    # B=1 takes a dedicated shortcut in the numpy kernel; the scan must
    # agree with it exactly
    ready = _ready_from_gaps(gaps, 0.5)
    a, b = _both_fills(ready, _lut(1, 0.012, 0.0), 1, replicas)
    _assert_fill_equal(a, b)


@settings(max_examples=10, deadline=None)
@given(
    gaps=st.lists(st.floats(min_value=0.0, max_value=0.05),
                  min_size=60, max_size=60),
    max_batch=st.integers(min_value=1, max_value=6),
    replicas=st.integers(min_value=1, max_value=3),
    frac1=st.floats(min_value=0.05, max_value=0.45),
    frac2=st.floats(min_value=0.5, max_value=0.95),
    delta1=st.integers(min_value=1, max_value=2),
)
def test_dynamic_pool_fill_bit_identical(gaps, max_batch, replicas,
                                         frac1, frac2, delta1):
    ready = _ready_from_gaps(gaps, 0.3)
    span = float(ready[-1]) if ready[-1] > 0 else 1.0
    events = [(frac1 * span, delta1), (frac2 * span, -1)]
    a, b = _both_fills(ready, _lut(max_batch, 0.008, 0.003),
                       max_batch, replicas, replica_events=events)
    _assert_fill_equal(a, b)


def test_zero_replicas_with_scale_up_events():
    # pool starts empty; the first add event brings capacity online
    ready = np.cumsum(np.full(40, 0.01))
    events = [(0.15, 2)]
    a, b = _both_fills(ready, _lut(4, 0.01, 0.002), 4, 0,
                       replica_events=events)
    _assert_fill_equal(a, b)


def test_simultaneous_arrivals_and_ties():
    ready = np.sort(np.concatenate(
        [np.cumsum(np.full(30, 0.02)), np.full(10, 0.3)]))
    a, b = _both_fills(ready, _lut(8, 0.015, 0.001), 8, 2)
    _assert_fill_equal(a, b)


def test_negative_lut_falls_back_to_numpy():
    # unprofiled batch size inside [1, eff]: the device kernel refuses
    # and the dispatcher must return the numpy result unchanged
    ready = np.cumsum(np.full(32, 0.01))
    lut = _lut(4, 0.01, 0.002)
    lut[3] = -1.0
    a, b = _both_fills(ready, lut, 4, 2)
    _assert_fill_equal(a, b)


def test_backend_kwarg_ignored_by_deadline_policies():
    # edf / slo-drop have no device kernels; backend="jax" must be a
    # harmless no-op there
    ready = np.cumsum(np.full(32, 0.01))
    lut = _lut(4, 0.01, 0.002)
    deadlines = ready + 0.25
    for policy in ("edf", "slo-drop"):
        a = simulate_stage(policy, ready, lut, 4, 2, deadline=deadlines)
        b = simulate_stage(policy, ready, lut, 4, 2, deadline=deadlines,
                           backend="jax")
        _assert_fill_equal(a, b)


def test_simulate_stage_rejects_unknown_backend():
    ready = np.cumsum(np.full(8, 0.01))
    with pytest.raises(ValueError, match="backend"):
        simulate_stage("fifo", ready, _lut(2, 0.01, 0.001), 2, 1,
                       backend="tpu")


def test_block_threshold_env_override():
    repo_src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    out = subprocess.run(
        [sys.executable, "-c",
         "import repro.sim.queueing as q; print(q._BLOCK_THRESHOLD)"],
        env={**os.environ, "REPRO_BLOCK_FILL_THRESHOLD": "123",
             "PYTHONPATH": repo_src},
        capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "123"


# -- percentile parity ------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(
    vals=st.lists(st.floats(min_value=-5.0, max_value=5.0),
                  min_size=1, max_size=120),
    p_i=st.integers(min_value=0, max_value=1000),
)
def test_percentile_bit_identical(vals, p_i):
    p = p_i / 10.0
    arr = np.asarray(vals, dtype=np.float64)
    host = float(np.percentile(arr, p))
    dev = float(jb.percentile_1d(arr, p))
    assert host == dev or (np.isnan(host) and np.isnan(dev))


@settings(max_examples=10, deadline=None)
@given(
    vals=st.lists(st.floats(min_value=0.0, max_value=2.0),
                  min_size=4, max_size=80),
    n_inf=st.integers(min_value=1, max_value=3),
    p_i=st.integers(min_value=900, max_value=1000),
)
def test_percentile_with_inf_tail(vals, n_inf, p_i):
    # dropped/never-completed queries surface as +inf latencies; the tail
    # percentiles must agree (including inf-inf interpolation -> nan)
    p = p_i / 10.0
    arr = np.asarray(list(vals) + [np.inf] * n_inf, dtype=np.float64)
    with np.errstate(invalid="ignore"):
        host = float(np.percentile(arr, p))
        dev = float(jb.percentile_1d(arr, p))
    assert host == dev or (np.isnan(host) and np.isnan(dev))


# -- session / grid parity --------------------------------------------------

def _poisson_trace(n, rate, seed=0):
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate, n))


def _base_config(bound):
    return PipelineConfig({
        s: StageConfig(stage.hardware_options[0], 1, 1)
        for s, stage in bound.pipeline.stages.items()
    })


def _sink_grid(bound, stage, hws, batches, reps):
    base = _base_config(bound)
    grid = []
    for hw in hws:
        for b in batches:
            for r in reps:
                cfg = base.copy()
                cfg.stage_configs[stage] = StageConfig(hw, b, r)
                grid.append(cfg)
    return grid


def test_grid_percentile_many_bit_identical_and_engaged():
    bound = get_motif("image-processing")
    engine = SimEngine(bound.pipeline, bound.profiles)
    arr = _poisson_trace(4000, 60.0, seed=3)
    grid = _sink_grid(bound, "classify", ("tpu-v5e-8", "tpu-v5e-4"),
                      (1, 2, 4, 8), range(1, 9))
    assert len(grid) >= jb._GRID_MIN_CANDIDATES

    host = engine.session(arr).percentile_many(grid, 99.0)

    calls = []
    orig = jb.grid_stage_percentiles

    def spy(*a, **kw):
        calls.append(len(a[0]))
        return orig(*a, **kw)

    jb.grid_stage_percentiles = spy
    try:
        dev = engine.session(arr, backend="jax").percentile_many(grid, 99.0)
    finally:
        jb.grid_stage_percentiles = orig

    assert calls, "device grid path did not engage"
    assert host == dev  # exact float equality, element-wise


def test_grid_ineligible_falls_back_to_host_loop():
    # two stages vary vs the pivot -> the device grid must decline and
    # the host loop must still serve identical answers
    bound = get_motif("image-processing")
    engine = SimEngine(bound.pipeline, bound.profiles)
    arr = _poisson_trace(3000, 50.0, seed=5)
    base = _base_config(bound)
    grid = []
    for b in (1, 2, 4, 8):
        for r in (1, 2, 3, 4, 5, 6):
            for pb in (1, 2):
                cfg = base.copy()
                cfg.stage_configs["classify"] = StageConfig("tpu-v5e-8", b, r)
                cfg.stage_configs["preprocess"] = StageConfig("cpu-1", pb, 2)
                grid.append(cfg)

    calls = []
    orig = jb.grid_stage_percentiles
    jb.grid_stage_percentiles = lambda *a, **kw: (
        calls.append(1), orig(*a, **kw))[1]
    try:
        dev = engine.session(arr, backend="jax").percentile_many(grid, 99.0)
    finally:
        jb.grid_stage_percentiles = orig
    host = engine.session(arr).percentile_many(grid, 99.0)

    assert not calls
    assert host == dev


def test_session_simulate_parity_classed_trace():
    # full-session parity on a mixed-SLO trace with a deadline policy in
    # the pipeline: device fills handle the fifo stages, numpy the rest
    bound = get_motif("image-processing")
    engine = SimEngine(bound.pipeline, bound.profiles)
    arr = _poisson_trace(2000, 40.0, seed=11)
    rng = np.random.default_rng(12)
    slo_s = np.where(rng.random(arr.size) < 0.5, 0.15, 0.6)
    cfg = _base_config(bound)
    cfg.stage_configs["classify"] = StageConfig("tpu-v5e-8", 4, 2)
    cfg.stage_configs["preprocess"] = StageConfig(
        "cpu-1", 2, 2, policy="slo-drop")

    host = engine.session(arr, slo_s=slo_s).simulate(cfg)
    old = jb._JAX_FILL_THRESHOLD
    jb._JAX_FILL_THRESHOLD = 0
    try:
        dev = engine.session(arr, slo_s=slo_s,
                             backend="jax").simulate(cfg)
    finally:
        jb._JAX_FILL_THRESHOLD = old
    np.testing.assert_array_equal(host.latency, dev.latency)


# -- plan-decision identity -------------------------------------------------

@pytest.mark.parametrize("motif", ["image-processing", "tf-cascade"])
def test_planner_decision_identity(motif):
    bound = get_motif(motif)
    arr = _poisson_trace(6000, 40.0, seed=7)
    slo = 0.5
    plans = {}
    for backend in ("numpy", "jax"):
        p = Planner(bound.pipeline, bound.profiles, backend=backend)
        plans[backend] = p.plan(arr, slo)
    a, b = plans["numpy"], plans["jax"]
    assert a.feasible == b.feasible
    if a.feasible:
        assert a.config.cache_key() == b.config.cache_key()
        assert a.cost_per_hr == b.cost_per_hr


@pytest.mark.parametrize("motif", ["image-processing", "video-monitoring"])
def test_beam_planner_decision_identity(motif):
    bound = get_motif(motif)
    arr = _poisson_trace(6000, 40.0, seed=9)
    slo = 0.6
    plans = {}
    for backend in ("numpy", "jax"):
        # pin beam_width: the jax default widens the frontier, which is
        # allowed to change the plan — identity is only promised at
        # equal width
        p = BeamPlanner(bound.pipeline, bound.profiles, beam_width=4,
                        backend=backend)
        plans[backend] = p.plan(arr, slo)
    a, b = plans["numpy"], plans["jax"]
    assert a.feasible == b.feasible
    if a.feasible:
        assert a.config.cache_key() == b.config.cache_key()
        assert a.cost_per_hr == b.cost_per_hr
