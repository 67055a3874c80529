"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def rmsnorm_ref(x: jnp.ndarray, scale: jnp.ndarray,
                eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def _expand_gqa(k: jnp.ndarray, h: int) -> jnp.ndarray:
    kv = k.shape[2]
    if kv == h:
        return k
    assert h % kv == 0
    return jnp.repeat(k, h // kv, axis=2)


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None,
                  scale: Optional[float] = None) -> jnp.ndarray:
    """q: (B,Sq,H,D); k,v: (B,Sk,KV,D[v]); mask broadcastable to
    (B,H,Sq,Sk). Returns (B,Sq,H,Dv). fp32 softmax.

    GQA is computed in grouped layout — q reshaped to (B,Sq,KV,G,D) —
    so shared KV heads are never materialized H/KV times (the expanded
    K/V of a 32k x 128-stream qwen2 decode step is 8x the cache, per
    layer, per read). Head-shaped masks (rare; none in this codebase)
    fall back to the expanded form.
    """
    b, sq, h, d = q.shape
    kv = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    head_mask = mask is not None and mask.ndim >= 4 and \
        mask.shape[-3] not in (1, None) and mask.shape[-3] == h and kv != h
    if kv == h or head_mask:
        k = _expand_gqa(k, h)
        v = _expand_gqa(v, h)
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        if mask is not None:
            s = jnp.where(mask, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        w = jnp.where(jnp.isnan(w), 0.0, w)  # fully-masked rows
        out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
        return out.astype(q.dtype)

    g = h // kv
    qg = q.reshape(b, sq, kv, g, d)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale      # (B,KV,G,Sq,Sk)
    if mask is not None:
        # broadcastable-to-(B,H,Sq,Sk) masks with a unit/absent head dim
        # broadcast over (KV,G) after inserting one axis
        m = mask
        while m.ndim < 4:
            m = m[None]
        s = jnp.where(m[:, :, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def causal_mask_ref(sq: int, sk: int, window: int = 0,
                    offset: int = 0) -> jnp.ndarray:
    qi = jnp.arange(sq)[:, None] + offset
    kj = jnp.arange(sk)[None, :]
    m = kj <= qi
    if window > 0:
        m &= (qi - kj) < window
    return m


def flash_attention_ref(q, k, v, causal: bool = True, window: int = 0,
                        scale: Optional[float] = None):
    """Oracle for the prefill flash kernel; q,k,v: (B,S,H|KV,D)."""
    sq, sk = q.shape[1], k.shape[1]
    mask = causal_mask_ref(sq, sk, window, offset=sk - sq) if causal else None
    return attention_ref(q, k, v, mask, scale)


def decode_attention_ref(q, k, v, valid_len, layer=0, window: int = 0,
                         scale: Optional[float] = None):
    """Oracle for the decode kernel, on the cache's head-major layout.

    q: (B,1,H,D); k,v: (L,B,KV,Smax,D) stacks, of which ``layer`` is
    read; valid_len: scalar or (B,) — number of populated cache slots
    (the new token is at index valid_len-1). Returns (B,1,H,Dv), fp32
    softmax.
    """
    b, _, h, d = q.shape
    k = jax.lax.dynamic_index_in_dim(k, layer, 0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False)
    kv, smax = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    vl = jnp.asarray(valid_len)
    if vl.ndim == 0:
        vl = jnp.full((b,), vl)
    kj = jnp.arange(smax)[None, :]
    mask = kj < vl[:, None]
    if window > 0:
        mask &= (vl[:, None] - 1 - kj) < window
    qg = q[:, 0].reshape(b, kv, h // kv, d).astype(jnp.float32)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, k.astype(jnp.float32)) * scale
    w = jax.nn.softmax(jnp.where(mask[:, None, None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", w, v.astype(jnp.float32))
    return out.reshape(b, 1, h, v.shape[-1]).astype(q.dtype)


def mamba_scan_ref(dt, x, b, c, a, h0):
    """Oracle for the mamba selective-scan kernel.

    dt, x: (B,S,D); b, c: (B,S,N); a: (D,N); h0: (B,D,N).
    Returns (y (B,S,D), h_last (B,D,N)). Sequential fp32 recurrence:
      h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t
      y_t = <h_t, C_t>
    """
    dtf = dt.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    af = a.astype(jnp.float32)

    def step(h, inp):
        dt_t, x_t, b_t, c_t = inp            # (B,D),(B,D),(B,N),(B,N)
        a_bar = jnp.exp(dt_t[..., None] * af)            # (B,D,N)
        h = a_bar * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        y_t = jnp.sum(h * c_t[:, None, :], axis=-1)      # (B,D)
        return h, y_t

    h_last, ys = jax.lax.scan(
        step, h0.astype(jnp.float32),
        (dtf.swapaxes(0, 1), xf.swapaxes(0, 1),
         bf.swapaxes(0, 1), cf.swapaxes(0, 1)))
    return ys.swapaxes(0, 1).astype(x.dtype), h_last.astype(h0.dtype)
