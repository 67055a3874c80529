"""Pallas TPU flash attention (prefill/train path).

Blockwise-softmax attention with explicit VMEM tiling via BlockSpec:
grid = (batch, q_heads, q_blocks, kv_blocks); the innermost grid dimension
iterates sequentially on TPU, so the online-softmax running state
(m, l, acc) lives in VMEM scratch and persists across kv blocks. GQA is
native: K/V blocks are indexed with ``h // group`` so shared KV heads are
fetched once per group without materializing the expanded KV.

Tiling: q blocks (BQ=128 rows) x kv blocks (BK=128) with the full head_dim
resident — MXU-aligned (128 lanes) and comfortably inside VMEM:
2*(BK*D) + BQ*D + BQ*BK fp32 words ~= 0.4 MiB for D=256.

Causal/sliding-window masking is applied with block-level iota compares;
fully-masked kv blocks still execute but contribute zero weight (block
skipping is a documented §Perf follow-up).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int,
                  bq: int, bk: int, sq: int, sk: int, n_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)      # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)      # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)      # (bk, dv)
    s = jnp.dot(q, k.T) * scale              # (bq, bk)

    # absolute positions; queries are offset by sk - sq so the causal
    # diagonal aligns when attending over a longer prefix
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (sk - sq)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        keep = k_pos <= q_pos
        if window > 0:
            keep &= (q_pos - k_pos) < window
        s = jnp.where(keep, s, NEG_INF)

    m_prev = m_ref[...]                       # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                    # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)           # (bq, 1)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,                # (B, Sq, H, D)
    k: jnp.ndarray,                # (B, Sk, KV, D)
    v: jnp.ndarray,                # (B, Sk, KV, Dv)
    causal: bool = True,
    window: int = 0,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BQ,
    block_k: int = DEFAULT_BK,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns (B, Sq, H, Dv)."""
    b, sq, h, d = q.shape
    _, sk, kv, dv = v.shape
    if h % kv:
        raise ValueError(f"q heads {h} not divisible by kv heads {kv}")
    group = h // kv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    if sq % bq or sk % bk:
        raise ValueError(f"seq lens ({sq},{sk}) must divide blocks ({bq},{bk})")
    n_q, n_kv = sq // bq, sk // bk

    # (B,S,H,D) -> (B,H,S,D): head_dim on the lane dimension
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, sq=sq, sk=sk, n_kv=n_kv)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, hh, qi, ki: (bb, hh // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, dv),
                         lambda bb, hh, qi, ki: (bb, hh // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dv),
                               lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
        name="flash_attention",
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.swapaxes(1, 2)
