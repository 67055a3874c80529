"""Jit-friendly kernel entry points with backend dispatch.

On TPU the Pallas kernels run natively, and a call the kernels cannot
serve (a shape that does not tile, a mask no kernel takes) raises rather
than quietly running the reference on the chip. Elsewhere (CPU hosts,
and any non-TPU backend) the pure-jnp references execute so models, smoke
tests, and the dry-run lowering all use the XLA path. Set
``REPRO_FORCE_PALLAS_INTERPRET=1`` to route through the Pallas kernels in
interpret mode (slow; used to exercise kernel code paths end-to-end).
"""

from __future__ import annotations

import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref, xla_flash
from repro.kernels.decode_attention import decode_attention as _pallas_decode
from repro.kernels.flash_attention import flash_attention as _pallas_flash
from repro.kernels.rmsnorm import rmsnorm as _pallas_rmsnorm

# Below this KV length the naive reference is used on non-TPU backends
# (compiles faster, and the S^2 scores are negligible); above it the
# blockwise xla_flash path keeps live scores O(bq x bk).
XLA_FLASH_MIN_SK = 2048


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def _force_interpret() -> bool:
    return os.environ.get("REPRO_FORCE_PALLAS_INTERPRET", "0") == "1"


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray,
            eps: float = 1e-6) -> jnp.ndarray:
    if _use_pallas():
        return _pallas_rmsnorm(x, scale, eps)
    if _force_interpret():
        return _pallas_rmsnorm(x, scale, eps, interpret=True)
    return ref.rmsnorm_ref(x, scale, eps)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              mask: Optional[jnp.ndarray], compute_dtype,
              kind: Optional[str] = None, window: int = 0,
              valid_len=None) -> jnp.ndarray:
    """General attention entry point.

    `kind` describes the mask structurally so the TPU path can use the
    flash kernels: "causal" | "full" | "decode". Off the TPU, kind None
    (an explicit irregular mask) and untileable shapes take the jnp
    reference; on the TPU they raise.
    """
    q = q.astype(compute_dtype)
    k = k.astype(compute_dtype)
    v = v.astype(compute_dtype)
    pallas = _use_pallas()
    interp = _force_interpret()
    sq, sk = q.shape[1], k.shape[1]
    if (pallas or interp) and kind in ("causal", "full"):
        if sq % min(128, sq) == 0 and sk % min(128, sk) == 0:
            return _pallas_flash(q, k, v, causal=(kind == "causal"),
                                 window=window, interpret=interp)
    if (pallas or interp) and kind == "decode" and valid_len is not None:
        if sk % min(512, sk) == 0:
            return _pallas_decode(q, k, v, valid_len, window=window,
                                  interpret=interp)
    if pallas:
        raise NotImplementedError(
            f"no Pallas attention kernel for kind={kind!r}, q {q.shape}, "
            f"k {k.shape} on TPU (flash needs Sq, Sk <= 128 or multiples "
            f"of 128; decode needs Smax <= 512 or a multiple of 512)")
    scale = 1.0 / math.sqrt(q.shape[-1])
    if kind in ("causal", "full") and mask is None:
        # XLA path for structural masks: blockwise flash above the size
        # threshold (keeps live scores O(bq x bk) — see xla_flash.py),
        # materialized mask below it.
        if sk >= XLA_FLASH_MIN_SK and xla_flash.supported(sq, sk):
            return xla_flash.flash_attention_xla(
                q, k, v, causal=(kind == "causal"), window=window,
                scale=scale)
        if kind == "causal":
            mask = ref.causal_mask_ref(sq, sk, window, offset=sk - sq)
    return ref.attention_ref(q, k, v, mask, scale)


def mamba_chunk(dt, x, b, c, a, h0):
    """One chunk of the mamba selective scan: fused on TPU, associative
    scan elsewhere.

    dt, x: (B,L,D); b, c: (B,L,N); a: (D,N); h0: (B,D,N) fp32.
    Returns (y (B,L,D) fp32, h_last (B,D,N) fp32).
    """
    if _use_pallas() or _force_interpret():
        from repro.kernels.mamba_scan import mamba_scan
        y, h = mamba_scan(dt, x, b, c, a, h0.astype(jnp.float32),
                          chunk=dt.shape[1],
                          interpret=_force_interpret())
        return y.astype(jnp.float32), h

    # XLA path: discretize + log-depth associative scan (parallel in L)
    a_bar = jnp.exp(dt[..., None].astype(jnp.float32)
                    * a.astype(jnp.float32))               # (B,L,D,N)
    bx = (dt * x).astype(jnp.float32)[..., None] * \
        b.astype(jnp.float32)[:, :, None, :]

    def comb(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    a_s, b_s = jax.lax.associative_scan(comb, (a_bar, bx), axis=1)
    h_all = a_s * h0.astype(jnp.float32)[:, None] + b_s
    y = jnp.einsum("bldn,bln->bld", h_all, c.astype(jnp.float32))
    return y, h_all[:, -1]
