"""Pallas TPU decode attention (single-token serve_step path).

Flash-decode-style attention of one query token against a long KV cache.
The KV length is the long axis, so the grid parallelizes over KV blocks:
grid = (batch, kv_heads, kv_blocks). All q heads in a GQA group are
processed together in one kernel instance — the group's queries form an
(G, D) tile that hits the MXU against each (BK, D) key block, turning a
memory-bound per-head matvec into a small matmul (TPU-native adaptation
of GPU flash-decode's warp-level split-K).

The cache is allocated to Smax but only ``valid_len`` slots are populated;
valid_len arrives via scalar prefetch (SMEM) and masks the tail block.
Online-softmax state persists in VMEM scratch across the innermost
(sequential) kv-block grid dimension.

Sliding-window decode (llama3.2-1b-sw long_500k config) masks keys older
than ``window`` positions behind the current token.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BK = 512
NEG_INF = -1e30


def _decode_kernel(vl_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   scale: float, window: int, bk: int, n_kv: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid_len = vl_ref[0]
    q = q_ref[0, 0].astype(jnp.float32)          # (G, D) — the GQA group
    k = k_ref[0, 0].astype(jnp.float32)          # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)          # (BK, Dv)
    s = jnp.dot(q, k.T) * scale                  # (G, BK)

    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = k_pos < valid_len
    if window > 0:
        keep &= (valid_len - 1 - k_pos) < window
    s = jnp.where(keep, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention(
    q: jnp.ndarray,                # (B, 1, H, D)
    k: jnp.ndarray,                # (B, Smax, KV, D)
    v: jnp.ndarray,                # (B, Smax, KV, Dv)
    valid_len,                     # scalar int — populated cache slots
    window: int = 0,
    scale: Optional[float] = None,
    block_k: int = DEFAULT_BK,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns (B, 1, H, Dv)."""
    b, sq, h, d = q.shape
    _, smax, kv, dv = v.shape
    assert sq == 1, "decode kernel processes exactly one new token"
    if h % kv:
        raise ValueError(f"q heads {h} not divisible by kv heads {kv}")
    group = h // kv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bk = min(block_k, smax)
    if smax % bk:
        raise ValueError(f"cache len {smax} must divide block {bk}")
    n_kv = smax // bk

    # (B,1,H,D) -> (B,KV,G,D): group queries per shared KV head
    qg = q[:, 0].reshape(b, kv, group, d)
    kt = k.swapaxes(1, 2)                        # (B,KV,Smax,D)
    vt = v.swapaxes(1, 2)
    vl = jnp.asarray(valid_len, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               bk=bk, n_kv=n_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, group, d), lambda bb, hh, ki, vl_: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, hh, ki, vl_: (bb, hh, ki, 0)),
            pl.BlockSpec((1, 1, bk, dv), lambda bb, hh, ki, vl_: (bb, hh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, dv),
                               lambda bb, hh, ki, vl_: (bb, hh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, dv), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, group, dv), q.dtype),
        name="decode_attention",
        interpret=interpret,
    )(vl, qg, kt, vt)
    return out.reshape(b, 1, h, dv)
