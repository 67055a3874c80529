"""Serving-state (KV cache / recurrent state) construction.

The cache mirrors the model's segment structure: for each segment, a dict
per block position whose leaves carry a leading ``repeat`` axis (so the
layer scan can consume them alongside the stacked parameters).

Dense K/V is head-major, the layout the decode kernel reads: the layer
scan carries the whole stack, each layer writes its rows at its own
index, and decode attends over it where it lies (``model._run_segment``).

Cache kinds per block:
  attn  (dense KV) : k,v            (repeat, B, KV, Smax, hd)
  attn  (MLA)      : c_kv, k_rope   (repeat, B, Smax, kr|rope)
  mamba            : h (repeat,B,D_in,N), conv (repeat,B,dc-1,D_in)
  mlstm            : C (repeat,B,H,dh,dh), n (repeat,B,H,dh)
  slstm            : h,c,n,m        (repeat, B, D)
  cross-attn (enc-dec): k,v        (repeat, B, F, KV, hd) over encoder
                   states, built at prefill.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax.numpy as jnp

from repro.models.config import ArchConfig, Block, Segment


def _attn_cache(cfg: ArchConfig, repeat: int, batch: int, smax: int,
                dtype) -> Dict[str, Any]:
    if cfg.use_mla:
        return {
            "c_kv": jnp.zeros((repeat, batch, smax, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((repeat, batch, smax, cfg.qk_rope_head_dim),
                                dtype),
        }
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((repeat, batch, cfg.num_kv_heads, smax, hd), dtype),
        "v": jnp.zeros((repeat, batch, cfg.num_kv_heads, smax, hd), dtype),
    }


def _block_cache(cfg: ArchConfig, block: Block, repeat: int, batch: int,
                 smax: int, dtype) -> Dict[str, Any]:
    if block.kind == "attn":
        return _attn_cache(cfg, repeat, batch, smax, dtype)
    if block.kind == "mamba":
        d_in = cfg.d_model * cfg.mamba_expand
        return {
            "h": jnp.zeros((repeat, batch, d_in, cfg.mamba_d_state),
                           jnp.float32),
            "conv": jnp.zeros((repeat, batch, cfg.mamba_d_conv - 1, d_in),
                              dtype),
        }
    if block.kind == "mlstm":
        d_in = cfg.d_model * cfg.xlstm_expand
        dh = d_in // cfg.num_heads
        return {
            "C": jnp.zeros((repeat, batch, cfg.num_heads, dh, dh),
                           jnp.float32),
            "n": jnp.zeros((repeat, batch, cfg.num_heads, dh), jnp.float32),
            # log-space stabilizer carried across decode steps
            "m": jnp.full((repeat, batch, cfg.num_heads), -1e30,
                          jnp.float32),
        }
    if block.kind == "slstm":
        d = cfg.d_model
        z = jnp.zeros((repeat, batch, d), jnp.float32)
        return {"h": z, "c": z, "n": z,
                "m": jnp.full((repeat, batch, d), -1e9, jnp.float32)}
    raise ValueError(block.kind)


def init_cache(cfg: ArchConfig, batch: int, smax: int,
               dtype=None) -> Tuple[Any, ...]:
    """Decode cache for the decoder stack; window-capped for SW attention."""
    dtype = dtype or cfg.cdtype
    cache = []
    for seg in cfg.segments:
        seg_cache = []
        for b in seg.blocks:
            # sliding-window attention never needs more than `window` slots
            s_eff = smax
            if b.kind == "attn" and cfg.sliding_window > 0:
                s_eff = min(smax, cfg.sliding_window)
            seg_cache.append(
                _block_cache(cfg, b, seg.repeat, batch, s_eff, dtype))
        cache.append(tuple(seg_cache))
    out = tuple(cache)
    if cfg.is_encoder_decoder:
        # cross-attention K/V over encoder outputs, filled at prefill;
        # one slot per repeat (enc-dec patterns carry one attn block each)
        hd = cfg.resolved_head_dim
        cross = []
        for seg in cfg.segments:
            cross.append({
                "k": jnp.zeros((seg.repeat, batch, cfg.encoder_max_frames,
                                cfg.num_kv_heads, hd), dtype),
                "v": jnp.zeros((seg.repeat, batch, cfg.encoder_max_frames,
                                cfg.num_kv_heads, hd), dtype),
            })
        return out, tuple(cross)
    return out, None


def cache_bytes(cfg: ArchConfig, batch: int, smax: int) -> int:
    """Analytic cache footprint (profiler/roofline helper)."""
    import numpy as np

    cache, cross = init_cache(cfg, 1, 8)  # tiny instantiation for structure
    del cache, cross
    total = 0
    itemsize = jnp.dtype(cfg.compute_dtype).itemsize
    for seg in cfg.segments:
        for b in seg.blocks:
            if b.kind == "attn":
                s_eff = min(smax, cfg.sliding_window) if cfg.sliding_window \
                    else smax
                if cfg.use_mla:
                    per = s_eff * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
                else:
                    per = 2 * s_eff * cfg.num_kv_heads * cfg.resolved_head_dim
            elif b.kind == "mamba":
                d_in = cfg.d_model * cfg.mamba_expand
                per = d_in * cfg.mamba_d_state * 2 + (cfg.mamba_d_conv - 1) * d_in
            elif b.kind == "mlstm":
                d_in = cfg.d_model * cfg.xlstm_expand
                dh = d_in // cfg.num_heads
                per = cfg.num_heads * (dh * dh + dh) * 2
            else:  # slstm
                per = 4 * cfg.d_model * 2
            total += seg.repeat * per * batch * itemsize
    return int(total)
