"""Neural building blocks for the model zoo — pure JAX, init/apply pairs.

Every layer is a pair of functions:
  ``init_<layer>(key, cfg) -> params``  (nested dict of jnp arrays)
  ``<layer>(params, cfg, x, ...) -> y`` (pure function)

Covered: RMSNorm, RoPE, GQA/MQA attention (full / causal / sliding-window /
cross), DeepSeek-style MLA (naive-expand prefill + absorbed decode),
SwiGLU MLP, scatter-based top-k MoE with capacity + aux loss, Mamba
selective-SSM block (chunked associative scan), and xLSTM mLSTM
(chunkwise-parallel) / sLSTM (sequential scan) cells.

Attention inner products route through ``repro.kernels.ops`` which
dispatches Pallas kernels on TPU and the jnp reference elsewhere.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig

Params = Dict[str, Any]


def _dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 1 else 1
    scale = scale if scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(cfg: ArchConfig, d: Optional[int] = None) -> Params:
    return {"scale": jnp.ones(d or cfg.d_model, cfg.pdtype)}


def rmsnorm(params: Params, cfg: ArchConfig, x: jnp.ndarray) -> jnp.ndarray:
    from repro.kernels import ops
    return ops.rmsnorm(x, params["scale"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., s, hd/2)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, \
        cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, h, hd), cfg.pdtype),
        "wk": _dense_init(ks[1], (d, kv, hd), cfg.pdtype),
        "wv": _dense_init(ks[2], (d, kv, hd), cfg.pdtype),
        "wo": _dense_init(ks[3], (h, hd, d), cfg.pdtype,
                          scale=1.0 / math.sqrt(h * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), cfg.pdtype)
        p["bk"] = jnp.zeros((kv, hd), cfg.pdtype)
        p["bv"] = jnp.zeros((kv, hd), cfg.pdtype)
    return p


def init_cross_attention(key, cfg: ArchConfig) -> Params:
    return init_attention(key, cfg)


def _expand_kv(k: jnp.ndarray, groups: int) -> jnp.ndarray:
    """(B,S,KV,hd) -> (B,S,KV*groups,hd) by repeat (GQA share)."""
    if groups == 1:
        return k
    return jnp.repeat(k, groups, axis=2)


def attention_core(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   mask: Optional[jnp.ndarray], compute_dtype,
                   kind: Optional[str] = None,
                   window: int = 0) -> jnp.ndarray:
    """q: (B,Sq,H,Dq) k: (B,Sk,H,Dq) v: (B,Sk,H,Dv) -> (B,Sq,H,Dv).

    Routed through kernels.ops (Pallas flash attention on TPU, blockwise
    xla_flash on other backends when `kind` describes the mask
    structurally)."""
    from repro.kernels import ops
    return ops.attention(q, k, v, mask, compute_dtype, kind=kind,
                         window=window)


def make_causal_mask(sq: int, sk: int, window: int = 0,
                     offset: int = 0) -> jnp.ndarray:
    """(sq, sk) boolean mask. query i attends key j iff
    j <= i+offset and (window==0 or i+offset-j < window)."""
    qi = jnp.arange(sq)[:, None] + offset
    kj = jnp.arange(sk)[None, :]
    m = kj <= qi
    if window > 0:
        m &= (qi - kj) < window
    return m


def attention(params: Params, cfg: ArchConfig, x: jnp.ndarray,
              positions: jnp.ndarray,
              mask: Optional[jnp.ndarray],
              kv_x: Optional[jnp.ndarray] = None,
              use_rope: bool = True,
              cache: Optional[Params] = None,
              cache_pos: Optional[jnp.ndarray] = None,
              kind: Optional[str] = None,
              layer=None,
              ) -> Tuple[jnp.ndarray, Optional[Params]]:
    """General GQA attention.

    * self-attention over x when kv_x is None
    * cross-attention over kv_x otherwise (no rope on cross)
    * with `cache` (dict k,v: the segment's head-major stacks
      (repeat,B,KV,Smax,hd)) and scalar `layer`, this layer's K/V are
      written into slice `layer` of the stacks: the whole prompt
      (prefill), or with scalar `cache_pos` the one new row (single-token
      decode, which then attends over the cache prefix in place).
    `kind` describes the mask structurally ("causal" | "full") so large
    sequences never materialize a dense mask or S^2 scores.
    Returns (output, updated_cache_or_None).
    """
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    groups = h // kvh
    src = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(cfg.cdtype))
    k = jnp.einsum("bsd,dhk->bshk", src, params["wk"].astype(cfg.cdtype))
    v = jnp.einsum("bsd,dhk->bshk", src, params["wv"].astype(cfg.cdtype))
    if "bq" in params:
        q = q + params["bq"].astype(cfg.cdtype)
        k = k + params["bk"].astype(cfg.cdtype)
        v = v + params["bv"].astype(cfg.cdtype)
    if use_rope and kv_x is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        from repro.models.sharding import constrain_kv
        smax = cache["k"].shape[3]
        # this layer's rows, head-major: (1, B, KV, S, hd)
        kh = k.astype(cache["k"].dtype).swapaxes(1, 2)[None]
        vh = v.astype(cache["v"].dtype).swapaxes(1, 2)[None]
        s = k.shape[1]
        if cache_pos is not None:
            # decode one token. Sliding-window caches (smax == window) are
            # ring buffers: slot = pos % window; RoPE is pre-applied so the
            # permuted order is harmless.
            slot = cache_pos % smax if cfg.sliding_window > 0 else cache_pos
        elif smax >= s:
            slot = 0
        elif cfg.sliding_window > 0:
            # prefill longer than the window: keep the tail, position p
            # in ring slot p % smax
            kh = jnp.roll(kh[:, :, :, -smax:], s % smax, axis=3)
            vh = jnp.roll(vh[:, :, :, -smax:], s % smax, axis=3)
            slot = 0
        else:
            raise ValueError(
                f"full-attention cache too small: smax={smax} < "
                f"prompt length {s} (did you forget the modality "
                f"prefix when sizing the cache?)")
        # one row (decode) or one layer (prefill) written into the stack
        # where it lies: the stacks are the layer scan's carry, so XLA
        # updates them in place.
        start = (layer, 0, 0, slot, 0)
        new_cache = {
            "k": constrain_kv(jax.lax.dynamic_update_slice(
                cache["k"], kh, start)),
            "v": constrain_kv(jax.lax.dynamic_update_slice(
                cache["v"], vh, start)),
        }
        if cache_pos is not None:
            from repro.kernels import ops
            out = ops.decode_attention(
                q.astype(cfg.cdtype), new_cache["k"], new_cache["v"],
                jnp.minimum(cache_pos + 1, smax), layer)
            return jnp.einsum("bshk,hkd->bsd", out,
                              params["wo"].astype(cfg.cdtype)), new_cache

    # GQA expansion happens inside the kernel/ref (KV heads stay compact).
    window = cfg.sliding_window if kv_x is None else 0
    from repro.kernels import ops
    out = ops.attention(q, k, v, mask, cfg.cdtype, kind=kind,
                        window=window)       # (B,Sq,H,hd)
    return jnp.einsum("bshk,hkd->bsd", out,
                      params["wo"].astype(cfg.cdtype)), new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3) — compressed-latent KV attention
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ArchConfig) -> Params:
    d, h = cfg.d_model, cfg.num_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": _dense_init(ks[0], (d, qr), cfg.pdtype),
        "q_norm": jnp.ones(qr, cfg.pdtype),
        "wq_b": _dense_init(ks[1], (qr, h, nope + rope_d), cfg.pdtype),
        "wkv_a": _dense_init(ks[2], (d, kr + rope_d), cfg.pdtype),
        "kv_norm": jnp.ones(kr, cfg.pdtype),
        "wkv_b_k": _dense_init(ks[3], (kr, h, nope), cfg.pdtype),
        "wkv_b_v": _dense_init(ks[4], (kr, h, vd), cfg.pdtype),
        "wo": _dense_init(ks[5], (h, vd, d), cfg.pdtype,
                          scale=1.0 / math.sqrt(h * vd)),
    }


def _mla_qc(params: Params, cfg: ArchConfig, x, positions):
    """Shared MLA projections: per-head q (nope+rope'd) and latent kv."""
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    kr = cfg.kv_lora_rank
    q_lat = jnp.einsum("bsd,dr->bsr", x, params["wq_a"].astype(cfg.cdtype))
    q_lat = _rms(q_lat, params["q_norm"].astype(cfg.cdtype), cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q_lat, params["wq_b"].astype(cfg.cdtype))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"].astype(cfg.cdtype))
    c_kv, k_rope = kv[..., :kr], kv[..., kr:]
    c_kv = _rms(c_kv, params["kv_norm"].astype(cfg.cdtype), cfg.norm_eps)
    k_rope = apply_rope(k_rope[..., None, :], positions, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope[..., 0, :]


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(
        x.dtype) * scale


def mla_attention(params: Params, cfg: ArchConfig, x: jnp.ndarray,
                  positions: jnp.ndarray, mask: Optional[jnp.ndarray],
                  cache: Optional[Params] = None,
                  cache_pos: Optional[jnp.ndarray] = None,
                  kind: Optional[str] = None,
                  ) -> Tuple[jnp.ndarray, Optional[Params]]:
    """Prefill/train: naive-expand form. Decode: absorbed form over the
    latent cache (c_kv, k_rope) — never materializes per-head K/V for the
    full context (the MLA memory saving)."""
    h = cfg.num_heads
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    q_nope, q_rope, c_kv, k_rope = _mla_qc(params, cfg, x, positions)

    if cache is not None and cache_pos is not None:
        # ---- absorbed decode ----
        cc = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), cache_pos, axis=1)
        cr = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype),
            cache_pos, axis=1)
        new_cache = {"c_kv": cc, "k_rope": cr}
        ccf, crf = cc.astype(cfg.cdtype), cr.astype(cfg.cdtype)
        # absorb W_UK into q: (B,1,H,nope) x (kr,H,nope) -> (B,1,H,kr)
        q_abs = jnp.einsum("bshn,rhn->bshr", q_nope,
                           params["wkv_b_k"].astype(cfg.cdtype))
        scores = (jnp.einsum("bshr,btr->bhst", q_abs, ccf)
                  + jnp.einsum("bshr,btr->bhst", q_rope, crf)) * scale
        smax = cc.shape[1]
        valid = jnp.arange(smax)[None, :] <= cache_pos
        scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
        w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(
            cfg.cdtype)
        out_lat = jnp.einsum("bhst,btr->bshr", w, ccf)
        out = jnp.einsum("bshr,rhv->bshv", out_lat,
                         params["wkv_b_v"].astype(cfg.cdtype))
        return jnp.einsum("bshv,hvd->bsd", out,
                          params["wo"].astype(cfg.cdtype)), new_cache

    # ---- train / prefill: expand latent to per-head K,V ----
    k_nope = jnp.einsum("btr,rhn->bthn", c_kv,
                        params["wkv_b_k"].astype(cfg.cdtype))
    v = jnp.einsum("btr,rhv->bthv", c_kv,
                   params["wkv_b_v"].astype(cfg.cdtype))
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :],
                                k_nope.shape[:3] + (k_rope.shape[-1],))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    out = attention_core(q, k, v, mask, cfg.cdtype, kind=kind)
    new_cache = None
    if cache is not None:
        new_cache = {
            "c_kv": jax.lax.dynamic_update_slice(
                cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, 0, 0)),
            "k_rope": jax.lax.dynamic_update_slice(
                cache["k_rope"], k_rope.astype(cache["k_rope"].dtype),
                (0, 0, 0)),
        }
    return jnp.einsum("bshv,hvd->bsd", out,
                      params["wo"].astype(cfg.cdtype)), new_cache


# ---------------------------------------------------------------------------
# Dense SwiGLU MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ArchConfig, d_ff: Optional[int] = None,
             gated: Optional[bool] = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    gated = (cfg.act == "swiglu") if gated is None else gated
    ks = jax.random.split(key, 3)
    p = {
        "wu": _dense_init(ks[1], (d, f), cfg.pdtype),
        "wd": _dense_init(ks[2], (f, d), cfg.pdtype),
    }
    if gated:
        p["wg"] = _dense_init(ks[0], (d, f), cfg.pdtype)
    return p


def mlp(params: Params, cfg: ArchConfig, x: jnp.ndarray) -> jnp.ndarray:
    u = jnp.einsum("bsd,df->bsf", x, params["wu"].astype(cfg.cdtype))
    if "wg" in params:  # swiglu
        g = jnp.einsum("bsd,df->bsf", x, params["wg"].astype(cfg.cdtype))
        h = jax.nn.silu(g) * u
    else:               # non-gated gelu (granite code models)
        h = jax.nn.gelu(u)
    return jnp.einsum("bsf,fd->bsd", h, params["wd"].astype(cfg.cdtype))


# ---------------------------------------------------------------------------
# MoE: scatter-dispatch top-k with static capacity (expert-parallel ready)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ArchConfig) -> Params:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (d, e), jnp.float32),  # fp32 router
        "wg": _dense_init(ks[1], (e, d, f), cfg.pdtype),
        "wu": _dense_init(ks[2], (e, d, f), cfg.pdtype),
        "wd": _dense_init(ks[3], (e, f, d), cfg.pdtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, gated=True,
                               d_ff=cfg.moe_d_ff * cfg.num_shared_experts)
    return p


def _moe_tokens(params: Params, cfg: ArchConfig, xt: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Route one token group (t, d) through the experts.

    Sort-based dispatch (MaxText-style): slot positions within each
    expert's capacity come from a stable argsort over expert ids, keeping
    peak memory O(t*k + E*C*D) instead of the O(t*E) one-hot cumsum.
    """
    t, d = xt.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)                   # (t,k)
    top_w = top_w / jnp.clip(top_w.sum(-1, keepdims=True), 1e-9, None)

    flat_e = top_i.reshape(-1)                                # (t*k,)
    # aux load-balance loss (switch-style) without one-hot
    me = probs.mean(axis=0)
    counts = jax.ops.segment_sum(jnp.ones_like(flat_e, jnp.float32),
                                 flat_e, num_segments=e)
    ce = counts / (t * k)
    aux = e * jnp.sum(me * ce) * cfg.router_aux_weight

    capacity = max(1, int(math.ceil(t * k / e * cfg.capacity_factor)))
    # Drop-free floor for small token counts (decode steps, smoke tests):
    # an expert receives at most `t` assignments, so capacity == t makes
    # routing exact at negligible memory cost when t is tiny.
    if t <= 64:
        capacity = max(capacity, t)

    # slot position of each assignment within its expert (stable sort)
    order = jnp.argsort(flat_e, stable=True)                  # (t*k,)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    pos_sorted = jnp.arange(t * k) - starts[sorted_e]
    pos = jnp.zeros(t * k, jnp.int32).at[order].set(
        pos_sorted.astype(jnp.int32))
    keep = pos < capacity

    # scatter tokens into (E, C, D) — E shards over `model` (EP)
    tok_idx = jnp.repeat(jnp.arange(t), k)
    src = jnp.where(keep[:, None], xt[tok_idx].astype(cfg.cdtype), 0.0)
    pe = jnp.where(keep, flat_e, e - 1)
    pp = jnp.where(keep, pos, capacity - 1)
    buf = jnp.zeros((e, capacity, d), cfg.cdtype).at[pe, pp].add(src)

    # expert FFNs: batched matmul, E sharded over `model`
    g = jnp.einsum("ecd,edf->ecf", buf, params["wg"].astype(cfg.cdtype))
    u = jnp.einsum("ecd,edf->ecf", buf, params["wu"].astype(cfg.cdtype))
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                   params["wd"].astype(cfg.cdtype))

    # gather back and combine with routing weights
    out_tk = jnp.where(keep[:, None], y[pe, pp], 0.0)         # (t*k, d)
    w = top_w.reshape(-1).astype(cfg.cdtype)
    out = jnp.zeros((t, d), cfg.cdtype).at[tok_idx].add(out_tk * w[:, None])

    if "shared" in params:
        out = out + mlp(params["shared"], cfg, xt[None]).reshape(t, d)
    return out, aux.astype(jnp.float32)


def moe(params: Params, cfg: ArchConfig, x: jnp.ndarray
        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routed experts with static capacity; returns (out, aux_loss).

    With ``cfg.moe_groups > 1`` tokens are routed in independent groups
    (group-limited capacity, as deployed EP systems do per-device): the
    group axis aligns with the mesh data axes so each shard dispatches its
    own tokens and the (G, E, C, D) buffer shards over (data, model).
    """
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    g = cfg.moe_groups
    if g > 1 and t % g == 0 and (t // g) >= 1:
        xg = xt.reshape(g, t // g, d)
        # Streaming (lax.map) vs parallel (vmap) groups: vmapping
        # materializes every group's (E, C, d_ff) expert hidden at once
        # (15 GiB/device fp32 on jamba prefill_32k), while scanning only
        # keeps one group live. But scan-AD's per-iteration residual
        # stacking costs small-expert models MORE than the vmap working
        # set (granite-moe train: 18 -> 35 GiB). Choose by the per-group
        # hidden size: stream when one group's hidden exceeds ~1 GiB
        # (jamba: 7.5 GiB -> map; deepseek: 2.7 GiB -> map;
        # granite-moe: 0.67 GiB -> vmap).
        tg = t // g
        e, k = max(cfg.num_experts, 1), max(cfg.num_experts_per_tok, 1)
        cap = max(1, int(math.ceil(tg * k / e * cfg.capacity_factor)))
        hidden_bytes = e * cap * max(cfg.moe_d_ff, 1) * 2
        if hidden_bytes > 1024 * 1024 * 1024:
            out, aux = jax.lax.map(
                lambda xx: _moe_tokens(params, cfg, xx), xg)
        else:
            out, aux = jax.vmap(
                lambda xx: _moe_tokens(params, cfg, xx))(xg)
        return out.reshape(b, s, d), aux.mean()
    out, aux = _moe_tokens(params, cfg, xt)
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Mamba selective SSM block (chunked scan)
# ---------------------------------------------------------------------------

def init_mamba(key, cfg: ArchConfig) -> Params:
    d = cfg.d_model
    d_in = d * cfg.mamba_expand
    st, dc = cfg.mamba_d_state, cfg.mamba_d_conv
    ks = jax.random.split(key, 6)
    return {
        "in_proj": _dense_init(ks[0], (d, 2 * d_in), cfg.pdtype),
        "conv_w": _dense_init(ks[1], (dc, d_in), cfg.pdtype, scale=0.5),
        "w_bc": _dense_init(ks[2], (d_in, 2 * st), cfg.pdtype),
        "w_dt": jnp.full((d_in,), 0.1, cfg.pdtype),
        "b_dt": jnp.full((d_in,), -2.0, cfg.pdtype),  # softplus(-2)~0.12
        "a_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, st + 1, dtype=jnp.float32), (d_in, st))
        ).astype(cfg.pdtype),
        "d_skip": jnp.ones(d_in, cfg.pdtype),
        "out_proj": _dense_init(ks[5], (d_in, d), cfg.pdtype),
    }


def _mamba_scan_chunk(a: jnp.ndarray, bx: jnp.ndarray, h0: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Linear recurrence h_t = a_t * h_{t-1} + bx_t within a chunk.

    a, bx: (B, L, D, N); h0: (B, D, N). Returns (h over chunk, h_last).
    """
    def comb(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    a_s, b_s = jax.lax.associative_scan(comb, (a, bx), axis=1)
    h = a_s * h0[:, None] + b_s
    return h, h[:, -1]


def mamba_block(params: Params, cfg: ArchConfig, x: jnp.ndarray,
                state: Optional[Params] = None,
                ) -> Tuple[jnp.ndarray, Optional[Params]]:
    """x: (B,S,D). With `state` (dict h:(B,D_in,N), conv:(B,dc-1,D_in)):
    recurrent continuation (decode uses S==1). Returns (y, new_state).

    FULLY CHUNK-STREAMED: the in-projection, causal conv, discretization,
    selective scan, gating and out-projection all run inside the chunk
    scan (conv tail and SSM state carried between chunks). Computing any
    of these full-sequence materializes (B,S,2*D_in)-class tensors —
    jamba-1.5-large prefill_32k paid ~90 GiB/device before this change
    (§Perf iterations 6 + 11).
    """
    b, s, d = x.shape
    d_in = d * cfg.mamba_expand
    st, dc = cfg.mamba_d_state, cfg.mamba_d_conv

    conv_tail0 = (state["conv"].astype(cfg.cdtype) if state is not None
                  else jnp.zeros((b, dc - 1, d_in), cfg.cdtype))
    h0 = (state["h"].astype(jnp.float32) if state is not None
          else jnp.zeros((b, d_in, st), jnp.float32))

    chunk = min(cfg.ssm_chunk, s)
    if s % chunk != 0:
        chunk = s  # fall back to single chunk for ragged lengths
    n_chunks = s // chunk

    w_in = params["in_proj"].astype(cfg.cdtype)
    conv_w = params["conv_w"].astype(cfg.cdtype)
    w_bc = params["w_bc"].astype(cfg.cdtype)
    w_dt = params["w_dt"].astype(cfg.cdtype)
    b_dt = params["b_dt"].astype(cfg.cdtype)
    d_skip = params["d_skip"].astype(jnp.float32)
    w_out = params["out_proj"].astype(cfg.cdtype)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))    # (D_in,N)

    def step(carry, x_c):
        h_carry, tail = carry                 # (B,D_in,N), (B,dc-1,D_in)
        xz = jnp.einsum("bld,de->ble", x_c, w_in)
        xs, z = jnp.split(xz, 2, axis=-1)
        xpad = jnp.concatenate([tail, xs], axis=1)
        new_tail = xpad[:, -(dc - 1):, :] if dc > 1 else tail
        xc = sum(xpad[:, i:i + chunk, :] * conv_w[i] for i in range(dc))
        xc = jax.nn.silu(xc)
        bc = jnp.einsum("ble,en->bln", xc, w_bc)
        b_c, c_c = jnp.split(bc.astype(jnp.float32), 2, axis=-1)
        dt = jax.nn.softplus(xc * w_dt + b_dt).astype(jnp.float32)
        xcf = xc.astype(jnp.float32)
        # fused Pallas selective-scan kernel on TPU; associative scan on
        # other backends (repro.kernels.ops.mamba_chunk)
        from repro.kernels import ops
        y_c, h_last = ops.mamba_chunk(dt, xcf, b_c, c_c, a, h_carry)
        y_c = (y_c + d_skip * xcf).astype(cfg.cdtype)
        y_c = y_c * jax.nn.silu(z)
        out_c = jnp.einsum("ble,ed->bld", y_c, w_out)
        return (h_last, new_tail), out_c

    x_ch = x.reshape(b, n_chunks, chunk, d).swapaxes(0, 1)
    (h_last, tail_last), out = jax.lax.scan(step, (h0, conv_tail0), x_ch)
    out = out.swapaxes(0, 1).reshape(b, s, d)
    new_state = None
    if state is not None:
        new_state = {"h": h_last.astype(state["h"].dtype),
                     "conv": tail_last.astype(state["conv"].dtype)}
    return out, new_state


# ---------------------------------------------------------------------------
# xLSTM cells
# ---------------------------------------------------------------------------

def init_mlstm(key, cfg: ArchConfig) -> Params:
    d = cfg.d_model
    d_in = d * cfg.xlstm_expand
    ks = jax.random.split(key, 7)
    return {
        "up": _dense_init(ks[0], (d, 2 * d_in), cfg.pdtype),
        "mq": _dense_init(ks[1], (d_in, d_in), cfg.pdtype),
        "mk": _dense_init(ks[2], (d_in, d_in), cfg.pdtype),
        "mv": _dense_init(ks[3], (d_in, d_in), cfg.pdtype),
        "w_i": _dense_init(ks[4], (d_in, cfg.num_heads), cfg.pdtype),
        "w_f": _dense_init(ks[5], (d_in, cfg.num_heads), cfg.pdtype),
        "b_i": jnp.zeros(cfg.num_heads, cfg.pdtype),
        "b_f": jnp.full((cfg.num_heads,), 3.0, cfg.pdtype),
        # per-head group-norm on the cell output (official xLSTM applies
        # MultiHeadLayerNorm here; without it denominator cancellation
        # lets |h| spike and training NaNs within ~20 steps)
        "out_norm": jnp.ones(d_in, cfg.pdtype),
        "down": _dense_init(ks[6], (d_in, d), cfg.pdtype),
    }


def mlstm_block(params: Params, cfg: ArchConfig, x: jnp.ndarray,
                state: Optional[Params] = None,
                ) -> Tuple[jnp.ndarray, Optional[Params]]:
    """mLSTM: matrix-memory cell (linear-attention-like), chunkwise
    parallel with the paper's LOG-SPACE STABILIZER.

    Unstabilized form:  C_t = f_t C_{t-1} + i_t v_t k_t^T ;
                        n_t = f_t n_{t-1} + i_t k_t ;
                        h_t = C_t q_t / max(|n_t . q_t|, 1)
    with exponential input gate i = exp(i~) and sigmoid forget gate. The
    raw exp overflows under training (observed: NaN after ~15 optimizer
    steps), so states are kept stabilized: every weight
    exp(F_t - F_s + i~_s) is divided by exp(m_t) where
    m_t = F_t + G_t,  G_t = max(m_prev, cummax_{s<=t}(i~_s - F_s)),
    F = intra-chunk cumulative log-forget. The carried (C, n, m) triple
    makes the recursion exact across chunks and decode steps.
    """
    b, s, d = x.shape
    h = cfg.num_heads
    d_in = d * cfg.xlstm_expand
    dh = d_in // h

    xu, z = jnp.split(
        jnp.einsum("bsd,de->bse", x, params["up"].astype(cfg.cdtype)),
        2, axis=-1)
    q = jnp.einsum("bse,ef->bsf", xu, params["mq"].astype(cfg.cdtype))
    k = jnp.einsum("bse,ef->bsf", xu, params["mk"].astype(cfg.cdtype))
    v = jnp.einsum("bse,ef->bsf", xu, params["mv"].astype(cfg.cdtype))
    q = q.reshape(b, s, h, dh).astype(jnp.float32) / math.sqrt(dh)
    k = k.reshape(b, s, h, dh).astype(jnp.float32)
    v = v.reshape(b, s, h, dh).astype(jnp.float32)

    logit_i = (jnp.einsum("bse,eh->bsh", xu, params["w_i"].astype(cfg.cdtype))
               + params["b_i"].astype(cfg.cdtype)).astype(jnp.float32)
    logit_f = (jnp.einsum("bse,eh->bsh", xu, params["w_f"].astype(cfg.cdtype))
               + params["b_f"].astype(cfg.cdtype)).astype(jnp.float32)
    log_f = jax.nn.log_sigmoid(logit_f)                  # (B,S,H), <= 0

    chunk = min(cfg.ssm_chunk, s)
    if s % chunk != 0:
        chunk = s
    n_chunks = s // chunk

    def reshape_c(t):
        return t.reshape((b, n_chunks, chunk) + t.shape[2:]).swapaxes(0, 1)

    qc, kc, vc = reshape_c(q), reshape_c(k), reshape_c(v)
    fic, iic = reshape_c(log_f), reshape_c(logit_i)

    def step(carry, inp):
        C, n, m_prev = carry         # (B,H,dh,dh), (B,H,dh), (B,H)
        qq, kk, vv, lf, li = inp
        F = jnp.cumsum(lf, axis=1)                         # (B,L,H)
        ss = li - F                                        # i~_s - F_s
        G = jnp.maximum(m_prev[:, None, :],
                        jax.lax.cummax(ss, axis=1))        # (B,L,H)
        m_t = F + G
        # carried-state weight exp(m_prev - G_t); key weight exp(s_s - G_t)
        w_carry = jnp.exp(m_prev[:, None, :] - G)          # (B,L,H) <= 1
        y_inter = jnp.einsum("blh,bhde,blhe->blhd", w_carry, C, qq)
        n_inter = jnp.einsum("blh,bhd,blhd->blh", w_carry, n, qq)
        # intra-chunk: w'_ts = exp(s_s - G_t) for s <= t (stabilized, <= 1).
        # Mask the EXPONENT, not the exp: for s > t the raw exponent is
        # unbounded-positive, exp overflows to inf, and the cotangent of
        # the subsequent where is 0 * inf = NaN (the backward-only NaN
        # that killed training while the forward loss stayed finite).
        mask = jnp.tril(jnp.ones((chunk, chunk), bool))
        expo = jnp.where(mask[None, :, :, None],
                         ss[:, None, :, :] - G[:, :, None, :], -1e30)
        w_rel = jnp.exp(jnp.minimum(expo, 0.0))                # (B,L,L,H)
        scores = jnp.einsum("blhd,bmhd->blmh", qq, kk) * w_rel
        y_intra = jnp.einsum("blmh,bmhd->blhd", scores, vv)
        n_intra = jnp.einsum("blmh,bmhd,blhd->blh", w_rel, kk, qq)
        y = y_inter + y_intra
        # exp(-m_t) saturates the output toward 0 once it exceeds the
        # numerator scale; clip the exponent so extreme log-forget sums
        # (F_t << 0 under training) cannot overflow to inf and poison
        # gradients.
        floor = jnp.exp(jnp.clip(-m_t, -40.0, 40.0))
        denom = jnp.maximum(jnp.abs(n_inter + n_intra), floor)
        y = y / denom[..., None]
        # carry to chunk end (t = L): same stabilized weights at G_L
        G_L = G[:, -1]                                     # (B,H)
        w_end = jnp.exp(ss - G_L[:, None, :])              # (B,L,H)
        cf = jnp.exp(m_prev - G_L)                         # (B,H)
        C_new = C * cf[:, :, None, None] + jnp.einsum(
            "blh,blhd,blhe->bhde", w_end, vv, kk)
        n_new = n * cf[:, :, None] + jnp.einsum(
            "blh,blhd->bhd", w_end, kk)
        m_new = F[:, -1] + G_L
        return (C_new, n_new, m_new), y

    if state is not None:
        C0 = state["C"].astype(jnp.float32)
        n0 = state["n"].astype(jnp.float32)
        m0 = state["m"].astype(jnp.float32)
    else:
        C0 = jnp.zeros((b, h, dh, dh), jnp.float32)
        n0 = jnp.zeros((b, h, dh), jnp.float32)
        m0 = jnp.full((b, h), -1e30, jnp.float32)
    (C_last, n_last, m_last), yc = jax.lax.scan(
        step, (C0, n0, m0), (qc, kc, vc, fic, iic))
    y = yc.swapaxes(0, 1).reshape(b, s, h, dh)
    # per-head group norm (see init_mlstm)
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + 1e-6)
    y = y.reshape(b, s, d_in).astype(cfg.cdtype) \
        * params["out_norm"].astype(cfg.cdtype)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", y, params["down"].astype(cfg.cdtype))
    new_state = None
    if state is not None:
        new_state = {"C": C_last.astype(state["C"].dtype),
                     "n": n_last.astype(state["n"].dtype),
                     "m": m_last.astype(state["m"].dtype)}
    return out, new_state


def init_slstm(key, cfg: ArchConfig) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    return {
        "w_x": _dense_init(ks[0], (d, 4 * d), cfg.pdtype),   # z,i,f,o from x
        "r_h": _dense_init(ks[1], (d, 4 * d), cfg.pdtype,
                           scale=0.5 / math.sqrt(d)),        # recurrent
        "bias": jnp.concatenate([
            jnp.zeros(2 * d), jnp.full((d,), 3.0), jnp.zeros(d)
        ]).astype(cfg.pdtype),
        "proj": _dense_init(ks[2], (d, d), cfg.pdtype),
    }


def slstm_block(params: Params, cfg: ArchConfig, x: jnp.ndarray,
                state: Optional[Params] = None,
                ) -> Tuple[jnp.ndarray, Optional[Params]]:
    """sLSTM: scalar-memory cell with exponential gating and stabilizer
    state m; inherently sequential (true recurrence through h)."""
    b, s, d = x.shape
    pre = jnp.einsum("bsd,de->bse", x,
                     params["w_x"].astype(cfg.cdtype)) + \
        params["bias"].astype(cfg.cdtype)
    r_h = params["r_h"].astype(cfg.cdtype)

    def step(carry, pre_t):
        h, c, n, m = carry
        gates = (pre_t + jnp.einsum("bd,de->be", h, r_h)).astype(jnp.float32)
        z_t, i_t, f_t, o_t = jnp.split(gates, 4, axis=-1)
        z_t = jnp.tanh(z_t)
        o_t = jax.nn.sigmoid(o_t)
        m_new = jnp.maximum(f_t + m, i_t)               # log-space stabilizer
        i_s = jnp.exp(i_t - m_new)
        f_s = jnp.exp(f_t + m - m_new)
        c_new = f_s * c + i_s * z_t
        n_new = f_s * n + i_s
        h_new = o_t * c_new / jnp.maximum(n_new, 1.0)
        return (h_new.astype(jnp.float32), c_new, n_new, m_new), h_new

    if state is not None:
        carry0 = (state["h"].astype(jnp.float32),
                  state["c"].astype(jnp.float32),
                  state["n"].astype(jnp.float32),
                  state["m"].astype(jnp.float32))
    else:
        zeros = jnp.zeros((b, d), jnp.float32)
        carry0 = (zeros, zeros, zeros, jnp.full((b, d), -1e9, jnp.float32))
    carry, hs = jax.lax.scan(step, carry0, pre.swapaxes(0, 1))
    y = hs.swapaxes(0, 1).astype(cfg.cdtype)
    out = jnp.einsum("bsd,de->bse", y, params["proj"].astype(cfg.cdtype))
    new_state = None
    if state is not None:
        h, c, n, m = carry
        new_state = {"h": h.astype(state["h"].dtype),
                     "c": c.astype(state["c"].dtype),
                     "n": n.astype(state["n"].dtype),
                     "m": m.astype(state["m"].dtype)}
    return out, new_state
