"""Pallas TPU fused RMSNorm kernel.

Row-tiled: grid over blocks of rows, each block normalizing (BR, D) in
VMEM with an fp32 mean-of-squares reduction fused with the scale multiply,
avoiding the separate variance/normalize/scale HLO round-trips through HBM.
D is the lane dimension. A row count up to ``block_rows`` is one block of
the whole array; above it BR = ``block_rows`` (a multiple of the 8-row
sublane tile) and the grid takes ceil(rows / BR) steps. A ragged last
block reads unspecified rows past the end and its out-of-range writes are
dropped, which is harmless because every row normalizes on its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 256


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y.astype(o_ref.dtype)
                  * s_ref[...].astype(o_ref.dtype))


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6,
            block_rows: int = DEFAULT_BLOCK_ROWS,
            interpret: bool = False) -> jnp.ndarray:
    """x: (..., D); scale: (D,). Returns x normalized*scale, x.dtype."""
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for dim in orig_shape[:-1]:
        rows *= dim
    if block_rows % 8:
        raise ValueError(f"block_rows={block_rows} is not a multiple of 8")
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    n = pl.cdiv(rows, br)

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        name="rmsnorm",
        interpret=interpret,
    )(x2, scale)
    return out.reshape(orig_shape)
