"""Pallas TPU kernel: mamba chunked selective-scan.

The roofline table (EXPERIMENTS.md §Roofline) classifies every
ssm/hybrid pair as memory-bound: the XLA path discretizes and scans the
(L, D, N) state update through HBM each chunk. This kernel fuses
discretization (a_bar = exp(dt*A), b_bar*x = dt*B*x), the linear
recurrence h_t = a_bar_t * h_{t-1} + bx_t, and the output contraction
y_t = <h_t, C_t> into one VMEM-resident pass, so HBM traffic per token
is just the inputs (dt, x, B, C) and output y — never the (L, D, N)
state trajectory.

Grid: (batch, d_blocks, n_chunks); the chunk axis iterates innermost
(sequentially on TPU), carrying the running state h in a VMEM scratch
tile — the same persistence pattern the flash kernel uses for its
softmax state. The state is kept transposed, (N, D_blk): D_blk on the
lane dim so each step is a lane-dense vector op, N on the sublane dim.
A is passed as (N, D) and B, C as (B, N, S) for the same layout.

The time recurrence runs as an in-kernel fori_loop over the chunk. Each
step reads its (1, D_blk) rows of dt and x straight from the refs with a
dynamic sublane slice, picks its (N, 1) columns of B and C with a
masked lane reduction (TPU Mosaic has no dynamic lane slice), and
writes its (1, D_blk) row of y back through a ``pl.ds`` store.
Operands are widened to f32 before the call so every dynamic row access
is on an unpacked 32-bit tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_D_BLOCK = 256


def _mamba_kernel(dt_ref, x_ref, bt_ref, ct_ref, at_ref, h0_ref,
                  y_ref, hout_ref, h_scratch, *,
                  chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scratch[...] = h0_ref[0]

    at = at_ref[...]                              # (N, D_blk)
    bt = bt_ref[0]                                # (N, chunk)
    ct = ct_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)

    def step(t, h):
        dt_t = dt_ref[0, pl.ds(t, 1), :]                      # (1, D_blk)
        x_t = x_ref[0, pl.ds(t, 1), :]
        pick = lane == t
        b_t = jnp.sum(jnp.where(pick, bt, 0.0), axis=1, keepdims=True)
        c_t = jnp.sum(jnp.where(pick, ct, 0.0), axis=1, keepdims=True)
        h = jnp.exp(dt_t * at) * h + b_t * (dt_t * x_t)       # (N, D_blk)
        # analysis: allow JAX01 — y_ref is the kernel's output VMEM ref;
        # a Pallas ref store runs on every step, it is not host state
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(h * c_t, axis=0, keepdims=True)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scratch[...])
    h_scratch[...] = h

    @pl.when(ci == n_chunks - 1)
    def _flush():
        hout_ref[0] = h


def mamba_scan(
    dt: jnp.ndarray,     # (B, S, D)   discretization step (post-softplus)
    x: jnp.ndarray,      # (B, S, D)   conv+silu'd input
    b: jnp.ndarray,      # (B, S, N)   input-dependent B
    c: jnp.ndarray,      # (B, S, N)   input-dependent C
    a: jnp.ndarray,      # (D, N)      state matrix (negative)
    h0: jnp.ndarray,     # (B, D, N)   carried state
    chunk: int = 256,
    d_block: int = DEFAULT_D_BLOCK,
    interpret: bool = False,
):
    """Returns (y (B,S,D), h_last (B,D,N)); fp32 state, x.dtype output."""
    bsz, s, d = dt.shape
    n = a.shape[1]
    chunk = min(chunk, s)
    if s % chunk:
        chunk = s
    n_chunks = s // chunk
    db = min(d_block, d)
    if d % db:
        db = d
    nd = d // db

    f32 = jnp.float32
    kernel = functools.partial(_mamba_kernel, chunk=chunk,
                               n_chunks=n_chunks)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(bsz, nd, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, db), lambda bb, di, ci: (bb, ci, di)),
            pl.BlockSpec((1, chunk, db), lambda bb, di, ci: (bb, ci, di)),
            pl.BlockSpec((1, n, chunk), lambda bb, di, ci: (bb, 0, ci)),
            pl.BlockSpec((1, n, chunk), lambda bb, di, ci: (bb, 0, ci)),
            pl.BlockSpec((n, db), lambda bb, di, ci: (0, di)),
            pl.BlockSpec((1, n, db), lambda bb, di, ci: (bb, 0, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, db), lambda bb, di, ci: (bb, ci, di)),
            pl.BlockSpec((1, n, db), lambda bb, di, ci: (bb, 0, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, d), f32),
            jax.ShapeDtypeStruct((bsz, n, d), f32),
        ],
        scratch_shapes=[pltpu.VMEM((n, db), f32)],
        interpret=interpret,
    )(dt.astype(f32), x.astype(f32),
      b.astype(f32).swapaxes(1, 2), c.astype(f32).swapaxes(1, 2),
      a.astype(f32).T, h0.astype(f32).swapaxes(1, 2))
    return y.astype(x.dtype), h_last.swapaxes(1, 2).astype(h0.dtype)
