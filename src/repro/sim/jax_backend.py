"""JAX (XLA) backend for the FIFO fill recurrence + device planner grids.

Two execution surfaces, both bit-identical to the numpy kernels in
:mod:`repro.sim.queueing` (float64 end to end; guarded by the parity
property suite in ``tests/test_jax_backend.py``):

* :func:`fifo_fill` — one stage's FIFO fill as a single ``jax.lax.scan``
  over batch boundaries, for static AND dynamic replica pools. The
  replica heap is carried as a *sorted buffer* (head = pool minimum;
  insertion is a compare-mask shift, no argmin/scatter), which is what
  makes the scan step cheap enough on CPU XLA — the heap's pop sequence
  depends only on the value multiset, so a sorted buffer with identical
  contents pops identical values and the outputs match the heap-driven
  numpy fill bit for bit.
* :func:`grid_stage_percentiles` — the accelerator-resident planner
  sweep: ``jax.vmap`` of the fill over a whole (hw, batch, replica,
  timeout) candidate grid (padded/masked per-candidate LUTs and replica
  pools), launched in ``REPRO_JAX_GRID_SEGMENTS`` segments so lanes
  that exhaust their queries early (large effective batches drain in
  ``k / batch`` steps) stop paying for the stragglers. Chunks are
  ordered by an expected-step-count heuristic so similarly-loaded lanes
  share a launch, and the cheap O(n) tail — batch expansion, scatter
  into arrival order, latency assembly, ``np.partition`` selection and
  the exact ``np.percentile`` lerp — runs on the host, where it is the
  *same* numpy ops the reference path uses (device sort/top_k of the
  full (C, n) latency block measured ~2x slower than the fills
  themselves on CPU XLA). :meth:`repro.sim.TraceSession.percentile_many`
  routes eligible candidate grids here when the session's ``backend``
  is ``"jax"``.

Float64 discipline: the repo's model/kernel stack runs jax in its f32
default; this module scopes ``jax.enable_x64(True)`` around every
trace and call instead of flipping the global flag, so simulator math is
IEEE-double (matching numpy) without disturbing the model zoo. That holds
on the CPU. A TPU emulates f64 without IEEE rounding: on a TPU v5e the
grid percentiles differed from numpy by up to ~5e-12 s while the plans
stayed identical (PERF.md), so bit identity is a CPU-only contract.

Auto-selection: single fills fall back to numpy below
``REPRO_JAX_FILL_THRESHOLD`` queries. ``benchmarks/bench_planner_scale.py
--backend jax`` measures the crossover; on the 1-core CPU hosts this
repo targets the scan never beats the blocked numpy kernel for a
*single* fill (XLA's per-step dispatch is load-invariant but ~10x the
numpy per-batch cost), so the default threshold is effectively "off" and
the win comes from grid width — hundreds of candidates amortized into
one launch. Set the env var lower to force the scan (the parity suite
does), or if a real accelerator is attached.
"""

from __future__ import annotations

import functools
import math
import os
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

_FAR_FUTURE = 1e18

# numpy-vs-jax crossover for a SINGLE fill (measured by
# bench_planner_scale --backend jax): on 1-core CPU hosts numpy wins at
# every trace length, so the default keeps single fills on numpy; the
# device path is for candidate GRIDS. Env-overridable for forcing.
_JAX_FILL_THRESHOLD = int(
    os.environ.get("REPRO_JAX_FILL_THRESHOLD", 1 << 62))
# device grid gating: fewer uncached candidates than this (or shorter
# fills) are cheaper through the host loop's shared caches
_GRID_MIN_CANDIDATES = int(os.environ.get("REPRO_JAX_GRID_MIN", 48))
_GRID_MIN_QUERIES = int(os.environ.get("REPRO_JAX_GRID_KMIN", 2048))
# candidates per compiled launch; grids pad up to a multiple so one
# grid shape compiles once per (k, Bmax, Rcap) bucket
_GRID_CHUNK = int(os.environ.get("REPRO_JAX_GRID_CHUNK", 256))
# the fill scan runs in ceil(k / _GRID_SEGMENTS)-step segments with a
# host early-exit between them: a lane forming full batches advances
# ~eff_batch queries per step, so backlogged chunks retire after k/b
# steps instead of burning the worst-case k (see grid_stage_percentiles)
_GRID_SEGMENTS = int(os.environ.get("REPRO_JAX_GRID_SEGMENTS", 8))


def _pow2_at_least(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length() if x > 1 else 1


# ---------------------------------------------------------------------------
# static-pool fill: one lax.scan over batch boundaries
# ---------------------------------------------------------------------------


def _static_fill_core(k: int, L: int, Bmax: int, Rcap: int,
                      with_timeout: bool):
    """Fill SEGMENT for one (trace length, segment length, batch pad,
    pool pad) shape: ``L`` scan steps from an explicit ``(ptr, free)``
    carry, so callers can chain segments and stop as soon as every lane
    has consumed its queue (a lane forming full batches needs only
    ~k/eff_batch steps; the worst case — singleton batches — still
    terminates after k total).

    The scan step mirrors the scalar recurrence in
    ``_FifoFill.run_static`` exactly: pop the pool minimum, form the
    batch at ``start = max(head arrival, free)``, apply the optional
    formation-timeout hold, complete at ``start + lut[b]``, push the
    completion back. ``ready_pad`` carries ``Bmax`` trailing ``+inf``
    entries so the fill window never reads out of bounds; the
    ``ptr + idx < k`` mask keeps padding (and any ``+inf`` arrivals
    from upstream starvation) out of the batch count exactly like the
    numpy kernel's ``limit = min(ptr + B, k)`` bound. ``with_timeout``
    is a compile-time flag: the planner's hot grids are timeout-free,
    and dropping the hold branch removes a second windowed count and a
    gather from every step.
    """
    idx_b = jnp.arange(Bmax)
    idx_r = jnp.arange(Rcap)

    def fill_seg(ready_pad, lut, eff_b, timeout_s, ptr0, free0):
        def step(carry, _):
            ptr, free = carry
            active = ptr < k
            f = free[0]
            r0 = ready_pad[ptr]
            start0 = jnp.maximum(r0, f)
            window = lax.dynamic_slice(ready_pad, (ptr,), (Bmax,))
            in_b = (idx_b < eff_b) & (ptr + idx_b < k)
            b0 = jnp.sum((window <= start0) & in_b).astype(jnp.int64)
            if with_timeout:
                # formation timeout (beyond-paper hold): only a batch
                # that cannot fill right now waits, until it fills or
                # expires
                limit_b = jnp.minimum(eff_b, k - ptr)
                hold_until = r0 + timeout_s
                fill_idx = ptr + eff_b - 1
                fill_t = jnp.where(fill_idx < k, ready_pad[fill_idx],
                                   _FAR_FUTURE)
                start1 = jnp.minimum(jnp.maximum(start0, fill_t),
                                     hold_until)
                need_hold = ((timeout_s > 0.0) & (b0 < limit_b)
                             & (hold_until > start0))
                start = jnp.where(need_hold, start1, start0)
                b = jnp.where(
                    need_hold,
                    jnp.sum((window <= start1) & in_b).astype(jnp.int64),
                    b0)
            else:
                start, b = start0, b0
            end = start + lut[b]
            b_out = jnp.where(active, b, 0)
            # sorted-buffer heap replacement: drop the head, insert the
            # completion at its rank (value multiset == the numpy heap's
            # at every step, so pops — and therefore outputs — match)
            shifted = jnp.concatenate([free[1:], free[-1:]])
            p = (jnp.sum(free < end) - 1).astype(jnp.int64)
            newfree = jnp.where(idx_r < p, shifted,
                                jnp.where(idx_r == p, end, free))
            free = jnp.where(active, newfree, free)
            return (ptr + b_out, free), (end, b_out)

        (ptr1, free1), (ends, counts) = lax.scan(
            step, (ptr0, free0), None, length=L)
        return ptr1, free1, ends, counts

    return fill_seg


@functools.lru_cache(maxsize=64)
def _static_fill_fn(k: int, L: int, Bmax: int, Rcap: int,
                    with_timeout: bool):
    """Jitted single-lane fill segment (the whole fill when L == k)."""
    return jax.jit(_static_fill_core(k, L, Bmax, Rcap, with_timeout))


@functools.lru_cache(maxsize=32)
def _grid_seg_fn(k: int, L: int, Bmax: int, Rcap: int, with_timeout: bool):
    """Jitted vmapped fill segment: one launch advances a whole chunk of
    candidates by up to L batch formations; the trace is broadcast, every
    per-candidate input (LUT, batch, timeout, carry) is mapped."""
    core = _static_fill_core(k, L, Bmax, Rcap, with_timeout)
    return jax.jit(jax.vmap(core, in_axes=(None, 0, 0, 0, 0, 0)))


def _static_pool(replicas: int, Rcap: int) -> np.ndarray:
    free0 = np.full(Rcap, np.inf)
    free0[:replicas] = 0.0
    return free0


def fill_static(ready: np.ndarray, lut: np.ndarray, eff_batch: int,
                replicas: int, timeout_s: float
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Static-pool FIFO fill on device; (done, batch sizes) aligned like
    the numpy kernel's outputs. Caller guarantees k >= 1, replicas >= 1,
    and a non-negative LUT over [1, eff_batch]."""
    k = int(ready.shape[0])
    Bmax = _pow2_at_least(eff_batch)
    Rcap = _pow2_at_least(replicas)
    ready_pad = np.concatenate([ready, np.full(Bmax, np.inf)])
    lut_pad = np.zeros(Bmax + 1)
    lut_pad[:eff_batch + 1] = lut[:eff_batch + 1]
    with jax.enable_x64(True):
        fn = _static_fill_fn(k, k, Bmax, Rcap, bool(timeout_s > 0.0))
        _, _, ends, counts = fn(
            jnp.asarray(ready_pad), jnp.asarray(lut_pad), eff_batch,
            float(timeout_s), jnp.zeros((), dtype=jnp.int64),
            jnp.asarray(_static_pool(replicas, Rcap)))
        ends = np.asarray(ends)
        counts = np.asarray(counts)
    done = np.repeat(ends, counts)        # sum(counts) == k exactly
    return done, counts[counts > 0]


# ---------------------------------------------------------------------------
# dynamic-pool fill: scan with in-step event application
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _dynamic_fill_fn(k: int, Bmax: int, Rcap: int, M: int, Mr: int, T: int):
    """Compiled dynamic-pool fill (``(t, +1/-1)`` replica scale events).

    Carries the sorted free buffer plus event cursors; each scan step is
    exactly one iteration of ``_FifoFill.run_dynamic``'s scalar loop:
    fast-forward to the next event when the pool is empty, or pop the
    minimum, apply events up to the dispatch instant, retire the popped
    replica if a scale-down is pending, else serve one batch. Removals
    retire in FIFO order of their event times (``rem_t``), matching
    ``ReplicaPool.pending_removals``. The trip count ``T`` upper-bounds
    serves + retires + fast-forwards + the starvation tail.
    """
    idx_b = jnp.arange(Bmax)
    idx_r = jnp.arange(Rcap)

    def insert_sorted(free, t):
        # shift-right insert at t's rank; the dropped tail slot is +inf
        # (the buffer is sized for the maximum possible pool)
        p = jnp.sum(free < t).astype(jnp.int64)
        shifted = jnp.concatenate([free[:1], free[:-1]])
        return jnp.where(idx_r < p, free,
                         jnp.where(idx_r == p, t, shifted))

    def fill_one(ready_pad, lut, eff_b, free0, n_free0, ev_t, ev_d, rem_t,
                 timeout_s):
        def apply_events(free, n_free, ev_i, rem_app, bound):
            # ReplicaPool.apply_events: push adds free at their t, queue
            # removals; the while_loop no-ops when bound precedes events
            def cond(s):
                _, _, i, _ = s
                return (i < M) & (ev_t[jnp.minimum(i, M - 1)] <= bound)

            def body(s):
                fr, nf, i, ra = s
                is_add = ev_d[i] > 0
                fr = jnp.where(is_add, insert_sorted(fr, ev_t[i]), fr)
                nf = nf + jnp.where(is_add, 1, 0)
                ra = ra + jnp.where(is_add, 0, 1)
                return fr, nf, i + 1, ra

            return lax.while_loop(cond, body, (free, n_free, ev_i, rem_app))

        def step(carry, _):
            ptr, free, n_free, ev_i, rem_app, rem_ret, starved = carry
            done_f = (ptr >= k) | starved
            empty = n_free == 0
            has_ev = ev_i < M
            is_ffwd = ~done_f & empty & has_ev
            is_starve = ~done_f & empty & ~has_ev
            is_pop = ~done_f & ~empty

            f = free[0]
            popped = jnp.concatenate([free[1:],
                                      jnp.full((1,), jnp.inf)])
            r0 = ready_pad[ptr]
            start = jnp.maximum(r0, f)
            # one bound drives all cases: the next event time for a
            # fast-forward, the dispatch instant for a serve, -inf
            # (no-op) otherwise
            bound = jnp.where(
                is_ffwd, ev_t[jnp.minimum(ev_i, M - 1)],
                jnp.where(is_pop, start, -jnp.inf))
            base_free = jnp.where(is_pop, popped, free)
            base_n = jnp.where(is_pop, n_free - 1, n_free)
            free2, n2, ev_i2, rem_app2 = apply_events(
                base_free, base_n, ev_i, rem_app, bound)

            pending = rem_ret < rem_app2
            retire = is_pop & pending & (
                rem_t[jnp.minimum(rem_ret, Mr - 1)] <= start)
            serve = is_pop & ~retire

            # batch formation (identical to the static step)
            window = lax.dynamic_slice(ready_pad, (ptr,), (Bmax,))
            in_b = (idx_b < eff_b) & (ptr + idx_b < k)
            b0 = jnp.sum((window <= start) & in_b).astype(jnp.int64)
            limit_b = jnp.minimum(eff_b, k - ptr)
            hold_until = r0 + timeout_s
            fill_idx = ptr + eff_b - 1
            fill_t = jnp.where(fill_idx < k, ready_pad[fill_idx],
                               _FAR_FUTURE)
            start1 = jnp.minimum(jnp.maximum(start, fill_t), hold_until)
            need_hold = ((timeout_s > 0.0) & (b0 < limit_b)
                         & (hold_until > start))
            bstart = jnp.where(need_hold, start1, start)
            b = jnp.where(
                need_hold,
                jnp.sum((window <= start1) & in_b).astype(jnp.int64), b0)
            end = bstart + lut[b]

            free3 = jnp.where(serve, insert_sorted(free2, end), free2)
            n3 = n2 + jnp.where(serve, 1, 0)
            cnt = jnp.where(serve, b, jnp.where(is_starve, k - ptr, 0))
            end_out = jnp.where(is_starve, _FAR_FUTURE, end)
            carry = (ptr + cnt, free3, n3, ev_i2, rem_app2,
                     rem_ret + jnp.where(retire, 1, 0),
                     starved | is_starve)
            return carry, (end_out, cnt, serve)

        init = (jnp.zeros((), dtype=jnp.int64), free0,
                n_free0.astype(jnp.int64), jnp.zeros((), dtype=jnp.int64),
                jnp.zeros((), dtype=jnp.int64),
                jnp.zeros((), dtype=jnp.int64), jnp.zeros((), dtype=bool))
        _, (ends, counts, is_batch) = lax.scan(step, init, None, length=T)
        done = jnp.repeat(ends, counts, total_repeat_length=k)
        return done, ends, counts, is_batch

    return jax.jit(fill_one)


def fill_dynamic(ready: np.ndarray, lut: np.ndarray, eff_batch: int,
                 replicas: int, replica_events: Sequence[Tuple[float, int]],
                 timeout_s: float) -> Tuple[np.ndarray, np.ndarray]:
    """Dynamic-pool FIFO fill on device (parity surface; the planner's
    hot grids are static-pool). Events are unit-expanded so each scan
    iteration applies at most one replica delta."""
    k = int(ready.shape[0])
    ev_t: List[float] = []
    ev_d: List[int] = []
    for t, d in replica_events:
        for _ in range(abs(int(d))):
            ev_t.append(float(t))
            ev_d.append(1 if d > 0 else -1)
    rem_t = [t for t, d in zip(ev_t, ev_d) if d < 0]
    M, Mr = len(ev_t), len(rem_t)
    adds = M - Mr
    Rcap = _pow2_at_least(max(replicas + adds, 1))
    Bmax = _pow2_at_least(eff_batch)
    T = k + M + Mr + 2
    ready_pad = np.concatenate([ready, np.full(Bmax, np.inf)])
    lut_pad = np.zeros(Bmax + 1)
    lut_pad[:eff_batch + 1] = lut[:eff_batch + 1]
    with jax.enable_x64(True):
        fn = _dynamic_fill_fn(k, Bmax, Rcap, M, max(Mr, 1), T)
        done, ends, counts, is_batch = fn(
            jnp.asarray(ready_pad), jnp.asarray(lut_pad), eff_batch,
            jnp.asarray(_static_pool(replicas, Rcap)),
            jnp.asarray(np.int64(replicas)),
            jnp.asarray(np.asarray(ev_t if M else [0.0])),
            jnp.asarray(np.asarray(ev_d if M else [0], dtype=np.int64)),
            jnp.asarray(np.asarray(rem_t if Mr else [_FAR_FUTURE])),
            float(timeout_s))
        done = np.asarray(done)
        counts = np.asarray(counts)
        is_batch = np.asarray(is_batch)
    return done, counts[(counts > 0) & is_batch]


# ---------------------------------------------------------------------------
# the queueing-kernel entry point
# ---------------------------------------------------------------------------


def fifo_fill(ready: np.ndarray, latency_lut: np.ndarray, eff_batch: int,
              replicas: int,
              replica_events: Optional[Sequence[Tuple[float, int]]],
              timeout_s: float
              ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Device FIFO fill, or None when the numpy kernel should run
    instead (fill below the crossover threshold, or a negative
    profiled latency — the sorted-buffer insert assumes
    completions never precede starts, like the numpy blocked kernel)."""
    k = int(ready.shape[0])
    if k < _JAX_FILL_THRESHOLD or k == 0:
        return None
    if float(np.min(latency_lut[1:eff_batch + 1])) < 0.0:
        return None
    if replica_events:
        return fill_dynamic(ready, latency_lut, eff_batch, replicas,
                            replica_events, timeout_s)
    if replicas <= 0:
        return None
    return fill_static(ready, latency_lut, eff_batch, replicas, timeout_s)


# ---------------------------------------------------------------------------
# exact np.percentile (linear interpolation) on device
# ---------------------------------------------------------------------------


def _quantile_params(n: int, p: float) -> Tuple[int, int, float]:
    """(prev_index, next_index, gamma) exactly as np.percentile computes
    them — same expression, same IEEE-754 doubles — so the device lerp
    reproduces the host value bit for bit."""
    # numpy's "linear" method computes the virtual index as
    # ``(n - 1) * q`` directly (NOT the generic alpha/beta formula, which
    # rounds differently in the last ulp — numpy's source carries a
    # comment to that effect).
    q = float(np.true_divide(p, 100))
    virt = (n - 1) * q
    if virt < 0.0:
        return 0, 0, 0.0
    if virt >= n - 1:
        return n - 1, n - 1, 0.0
    prev = int(math.floor(virt))
    return prev, prev + 1, virt - prev


def _host_lerp(a: np.ndarray, b: np.ndarray, t: float) -> np.ndarray:
    """numpy's ``_lerp`` verbatim (the t >= 0.5 branch computes from b).

    Runs on HOST floats: XLA contracts ``a + diff * t`` into an FMA,
    which is one ulp off np.percentile — so the device computes only the
    sort + two order-statistic gathers and the final interpolation stays
    in IEEE-faithful host arithmetic."""
    diff = b - a
    res = a + diff * t
    if t >= 0.5:
        res = b - diff * (1.0 - t)
    return res


def percentile_1d(values: np.ndarray, p: float) -> float:
    """np.percentile(values, p) with the sort on device — bit-identical
    (parity-tested, including +inf/FAR_FUTURE tails)."""
    n = int(values.shape[0])
    if n == 0:
        return 0.0
    prev, nxt, gamma = _quantile_params(n, p)
    with jax.enable_x64(True):
        s = jnp.sort(jnp.asarray(values))
        a, b = float(s[prev]), float(s[nxt])
    return float(_host_lerp(np.float64(a), np.float64(b), gamma))


# ---------------------------------------------------------------------------
# the vmapped (hw, batch, replica) candidate grid
# ---------------------------------------------------------------------------


def _expected_steps(k: float, lam: float, lut: np.ndarray, eff: int,
                    r: int) -> float:
    """Rough scan-step count for one lane: k / expected batch size.

    Expected fullness ~ arrivals per replica-service-time, capped at the
    effective batch. Heuristic only — used to group lanes whose fills
    retire after a similar number of steps so the segmented scan's
    early-exit actually fires (one underloaded singleton-batch lane
    would otherwise pin its whole chunk at the worst-case k steps)."""
    service = float(lut[eff])
    if service <= 0.0 or r <= 0:
        return k
    fullness = min(float(eff), max(1.0, lam * service / r))
    return k / fullness


def grid_stage_percentiles(
    sorted_ready: np.ndarray,
    order: np.ndarray,
    base_last: np.ndarray,
    arrivals: np.ndarray,
    rpc_delay_s: float,
    luts: Sequence[np.ndarray],
    eff_batches: Sequence[int],
    replicas: Sequence[int],
    timeouts: Sequence[float],
    p: float,
) -> np.ndarray:
    """Score a candidate grid that varies ONE sink stage, on device.

    ``sorted_ready``/``order`` are the varied stage's (fixed) input
    queue; ``base_last`` is the accumulated completion maximum over
    every *other* stage (they are candidate-invariant because the varied
    stage has no descendants). Per candidate: LUT, effective batch,
    replica count, formation timeout. Returns one ``np.percentile``-
    bit-identical latency percentile per candidate.

    Division of labor (1-core CPU measurements drove this split): the
    device runs ONLY the vmapped fill scan — in ceil(k/_GRID_SEGMENTS)-
    step segments, chunks ordered by expected step count, stopping as
    soon as every lane in a chunk has drained — while batch-boundary
    expansion, latency assembly, and the percentile *selection*
    (``np.partition``, O(n) vs a device sort's O(n log n)) run on host.
    Host assembly is also what makes bit-identity trivial here: it is
    numpy arithmetic, the same ops in the same order as the reference
    session path.
    """
    C = len(luts)
    k = int(sorted_ready.shape[0])
    n = int(arrivals.shape[0])
    Bmax = _pow2_at_least(max(eff_batches))
    Rcap = _pow2_at_least(max(replicas))
    prev, nxt, gamma = _quantile_params(n, p)
    ready_pad = np.concatenate([sorted_ready, np.full(Bmax, np.inf)])
    chunk = min(_GRID_CHUNK, max(_pow2_at_least(C) // 2, 32))
    L = max(1, -(-k // _GRID_SEGMENTS))
    luts_pad = np.zeros((C, Bmax + 1))
    for i, lut in enumerate(luts):
        e = int(eff_batches[i])
        luts_pad[i, :e + 1] = lut[:e + 1]
    eff_arr = np.asarray(eff_batches, dtype=np.int64)
    tmo_arr = np.asarray(timeouts, dtype=np.float64)
    free0 = np.full((C, Rcap), np.inf)
    for i, r in enumerate(replicas):
        free0[i, :int(r)] = 0.0
    span = float(sorted_ready[-1] - sorted_ready[0]) if k > 1 else 1.0
    lam = k / max(span, 1e-12)
    perm = np.argsort([
        _expected_steps(k, lam, luts_pad[i], int(eff_arr[i]),
                        int(replicas[i]))
        for i in range(C)
    ], kind="stable")
    out = np.empty(C)
    kth = (prev, nxt) if nxt > prev else (prev,)
    with jax.enable_x64(True):
        ready_j = jnp.asarray(ready_pad)
        for s in range(0, C, chunk):
            lanes = perm[s:s + chunk]
            v = len(lanes)
            pad = chunk - v
            lu = np.pad(luts_pad[lanes], ((0, pad), (0, 0)))
            eb = np.pad(eff_arr[lanes], (0, pad), constant_values=1)
            tm = np.pad(tmo_arr[lanes], (0, pad))
            fr = np.pad(free0[lanes], ((0, pad), (0, 0)),
                        constant_values=np.inf)
            if pad:
                fr[v:, 0] = 0.0           # keep padded lanes well-formed
            fn = _grid_seg_fn(k, L, Bmax, Rcap,
                              bool(np.any(tm > 0.0)))
            ptr = np.zeros(chunk, dtype=np.int64)
            ptr[v:] = k                   # padded lanes start drained
            ptr_j = jnp.asarray(ptr)
            fr_j = jnp.asarray(fr)
            lu_j, eb_j, tm_j = (jnp.asarray(lu), jnp.asarray(eb),
                                jnp.asarray(tm))
            ends_parts, counts_parts = [], []
            while True:
                ptr_j, fr_j, ends, counts = fn(ready_j, lu_j, eb_j, tm_j,
                                               ptr_j, fr_j)
                ends_parts.append(np.asarray(ends))
                counts_parts.append(np.asarray(counts))
                if bool(np.all(np.asarray(ptr_j) >= k)):
                    break
            ends_all = np.concatenate(ends_parts, axis=1)
            counts_all = np.concatenate(counts_parts, axis=1)
            for j in range(v):
                done = np.repeat(ends_all[j], counts_all[j])
                comp = np.full(n, -np.inf)
                comp[order] = done
                last = np.maximum(base_last, comp)
                lat = last - arrivals + rpc_delay_s
                part = np.partition(lat, kth)
                out[lanes[j]] = _host_lerp(part[prev], part[nxt], gamma)
    return out
