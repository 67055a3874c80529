"""Model assembly: init / train forward / prefill / decode for all six
architecture families, driven entirely by ``ArchConfig``.

Layer stacks execute as ``jax.lax.scan`` over each segment's ``repeat``
axis (parameters and caches carry a leading repeat dim), which keeps the
HLO size independent of depth — essential for lowering 61-88 layer
configs quickly and for the multi-pod dry-run.

Entry points (all pure, jit-able):
  Model.init(key)                                    -> params
  Model.forward(params, batch)                       -> (logits, aux_loss)
  Model.loss(params, batch)                          -> scalar
  Model.prefill(params, batch, smax)                 -> (last_logits, cache)
  Model.decode_step(params, token, pos, cache)       -> (logits, cache)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import ArchConfig, Block, Segment
from repro.models.kvcache import init_cache
from repro.models.sharding import constrain_batch

Params = Dict[str, Any]

AUDIO_FEAT_DIM = 128     # stub mel/conv frontend feature width
IMAGE_FEAT_DIM = 1024    # stub ViT patch-embedding width


def _cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Per-token CE that stays vocab-sharding-friendly.

    ``take_along_axis`` over a vocab-sharded logits tensor lowers to a
    gather across the sharded axis, which XLA resolves by replicating the
    full (B,S,V) logits on every device (observed: 101 GiB/device for
    whisper-small train_4k). The masked-sum form keeps every op either
    elementwise or a vocab-axis reduction — both shard cleanly (partial
    reduce + small all-reduce), so the logits stay model-sharded.
    """
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(lf.max(axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    iota = jnp.arange(lf.shape[-1], dtype=targets.dtype)
    tgt_logit = jnp.sum(
        jnp.where(iota[None, None, :] == targets[..., None], lf, 0.0),
        axis=-1)
    return lse - tgt_logit


# ---------------------------------------------------------------------------
# per-block init / apply
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ArchConfig, block: Block,
                cross_attn: bool) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {"norm1": L.init_rmsnorm(cfg)}
    if block.kind == "attn":
        p["core"] = L.init_mla(ks[0], cfg) if cfg.use_mla \
            else L.init_attention(ks[0], cfg)
    elif block.kind == "mamba":
        p["core"] = L.init_mamba(ks[0], cfg)
    elif block.kind == "mlstm":
        p["core"] = L.init_mlstm(ks[0], cfg)
    elif block.kind == "slstm":
        p["core"] = L.init_slstm(ks[0], cfg)
    if cross_attn and block.kind == "attn":
        p["norm_cross"] = L.init_rmsnorm(cfg)
        p["cross"] = L.init_cross_attention(ks[1], cfg)
    if block.ffn == "dense":
        p["norm2"] = L.init_rmsnorm(cfg)
        p["ffn"] = L.init_mlp(ks[2], cfg)
    elif block.ffn == "moe":
        p["norm2"] = L.init_rmsnorm(cfg)
        p["ffn"] = L.init_moe(ks[2], cfg)
    return p


def _apply_block(p: Params, cfg: ArchConfig, block: Block, x: jnp.ndarray,
                 positions: jnp.ndarray, mask: Optional[jnp.ndarray],
                 mask_kind: Optional[str],
                 cache: Optional[Params], cache_pos,
                 enc_out: Optional[jnp.ndarray],
                 cross_cache: Optional[Params],
                 layer=None,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[Params],
                            Optional[Params]]:
    """Returns (x, aux_loss, new_cache, new_cross_cache). A dense-KV
    attention block's `cache` is its segment's whole stack, written at
    index `layer` (see ``_run_segment``)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.rmsnorm(p["norm1"], cfg, x)
    new_cache = None
    # mask_kind describes the mask structurally ("causal"/"full"/None for
    # decode) so the attention path never materializes S^2 masks/scores.
    struct_kind = mask_kind if mask_kind in ("causal", "full") else None
    if block.kind == "attn":
        if cfg.use_mla:
            out, new_cache = L.mla_attention(p["core"], cfg, h, positions,
                                             mask, cache, cache_pos,
                                             kind=struct_kind)
        else:
            out, new_cache = L.attention(p["core"], cfg, h, positions, mask,
                                         cache=cache, cache_pos=cache_pos,
                                         kind=struct_kind, layer=layer)
    elif block.kind == "mamba":
        out, new_cache = L.mamba_block(p["core"], cfg, h, cache)
    elif block.kind == "mlstm":
        out, new_cache = L.mlstm_block(p["core"], cfg, h, cache)
    else:
        out, new_cache = L.slstm_block(p["core"], cfg, h, cache)
    x = x + out

    new_cross = None
    if "cross" in p:
        h = L.rmsnorm(p["norm_cross"], cfg, x)
        if enc_out is not None:
            out, _ = L.attention(p["cross"], cfg, h, positions, mask=None,
                                 kv_x=enc_out, use_rope=False, kind="full")
            if cross_cache is not None:
                # populate cross K/V once (prefill)
                ck = jnp.einsum("bsd,dhk->bshk", enc_out,
                                p["cross"]["wk"].astype(cfg.cdtype))
                cv = jnp.einsum("bsd,dhk->bshk", enc_out,
                                p["cross"]["wv"].astype(cfg.cdtype))
                new_cross = {"k": ck.astype(cross_cache["k"].dtype),
                             "v": cv.astype(cross_cache["v"].dtype)}
        else:
            # decode: attend over cached encoder K/V
            q = jnp.einsum("bsd,dhk->bshk", h,
                           p["cross"]["wq"].astype(cfg.cdtype))
            from repro.kernels import ops
            o = ops.attention(q, cross_cache["k"].astype(cfg.cdtype),
                              cross_cache["v"].astype(cfg.cdtype),
                              None, cfg.cdtype, kind="full")
            out = jnp.einsum("bshk,hkd->bsd", o,
                             p["cross"]["wo"].astype(cfg.cdtype))
            new_cross = cross_cache
        x = x + out

    if block.ffn == "dense":
        h = L.rmsnorm(p["norm2"], cfg, x)
        x = x + L.mlp(p["ffn"], cfg, h)
    elif block.ffn == "moe":
        h = L.rmsnorm(p["norm2"], cfg, x)
        out, aux = L.moe(p["ffn"], cfg, h)
        x = x + out
    return x, aux, new_cache, new_cross


# ---------------------------------------------------------------------------
# segment execution (scan over repeats)
# ---------------------------------------------------------------------------

def _init_segment(key, cfg: ArchConfig, seg: Segment,
                  cross_attn: bool) -> Tuple[Params, ...]:
    out = []
    for bi, block in enumerate(seg.blocks):
        keys = jax.random.split(jax.random.fold_in(key, bi), seg.repeat)
        stacked = jax.vmap(
            lambda k, blk=block: _init_block(k, cfg, blk, cross_attn)
        )(keys)
        out.append(stacked)
    return tuple(out)


def _run_segment(params_stack, cfg: ArchConfig, seg: Segment, x,
                 positions, mask, mask_kind,
                 cache_stack=None, cache_pos=None,
                 enc_out=None, cross_stack=None):
    """Scan over the repeat axis. Returns (x, aux_sum, new_cache_stack,
    new_cross_stack).

    Dense-KV caches ride in the scan's carry, whole: each layer writes
    its rows into the stacks at its own index and the decode kernel
    reads them there, so no layer's cache is sliced out, copied or
    restacked. Every other cache (MLA latents, recurrent state) is
    scanned per layer."""
    has_cache = cache_stack is not None
    has_cross = cross_stack is not None
    # dense K/V (MHA/GQA/MQA, ring buffers included) is carried
    dense = tuple(has_cache and b.kind == "attn" and not cfg.use_mla
                  for b in seg.blocks)

    # enc-dec segments carry exactly one attention block per pattern unit
    # (whisper), so one cross K/V slot per repeat.
    if has_cross:
        n_attn = sum(1 for b in seg.blocks if b.kind == "attn")
        assert n_attn == 1, "enc-dec pattern must have exactly 1 attn block"

    def body(carry, xs):
        # re-pin the residual stream each layer: without this XLA may
        # resolve the FSDP weight/batch axis conflict by replicating
        # activations (see sharding.constrain_batch).
        h, kv = carry
        h = constrain_batch(h)
        layer, blk_params, scanned, cross_c = xs
        aux_sum = jnp.zeros((), jnp.float32)
        new_kv, new_scanned = list(kv), list(scanned)
        new_cross = cross_c
        for bi, block in enumerate(seg.blocks):
            cc = cross_c if (has_cross and block.kind == "attn") else None
            c = kv[bi] if dense[bi] else scanned[bi]
            h, aux, nc, ncross = _apply_block(
                blk_params[bi], cfg, block, h, positions, mask, mask_kind,
                c, cache_pos, enc_out, cc, layer)
            aux_sum = aux_sum + aux
            if dense[bi]:
                new_kv[bi] = nc
            elif has_cache and nc is not None:
                new_scanned[bi] = nc
            if ncross is not None:
                new_cross = ncross
        # Megatron-style sequence parallelism at the layer boundary: the
        # carried residual (== the activation the remat scan saves per
        # layer) is seq-sharded over `model`; XLA inserts the all-gather
        # at the next layer's entry. Shrinks the saved-activation stack
        # (and XLA's fp32-widened copy of it) by the model-axis size.
        # ONLY for attention-bearing segments: in pure SSM/xLSTM
        # segments the seq axis is reshaped into (chunks, chunk) for the
        # recurrent scan and XLA propagates the seq sharding onto the
        # chunk axis — an all-gather inside EVERY chunk step (measured:
        # 1.06 s/step of collectives on xlstm-125m train_4k).
        if any(blk.kind == "attn" for blk in seg.blocks):
            h = constrain_batch(h, ("model",))
        else:
            h = constrain_batch(h)
        return (h, tuple(new_kv)), (aux_sum, tuple(new_scanned), new_cross)

    if cfg.remat:
        body = jax.checkpoint(body)

    caches = cache_stack if has_cache else (None,) * len(seg.blocks)
    kv = tuple(c if d else None for c, d in zip(caches, dense))
    scanned = tuple(None if d else c for c, d in zip(caches, dense))
    (x, kv), (aux, scanned, new_cross) = jax.lax.scan(
        body, (x, kv), (jnp.arange(seg.repeat), params_stack, scanned,
                        cross_stack))
    new_cache = None
    if has_cache:
        new_cache = tuple(k if d else c for k, c, d in zip(kv, scanned, dense))
    return x, aux.sum(), new_cache, new_cross


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Model:
    cfg: ArchConfig

    # ------------------------------------------------------------------ init
    def init(self, key) -> Params:
        cfg = self.cfg
        ks = jax.random.split(key, 8)
        p: Params = {
            "embed": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model))
                      * 0.02).astype(cfg.pdtype),
            "final_norm": L.init_rmsnorm(cfg),
            "segments": tuple(
                _init_segment(jax.random.fold_in(ks[1], i), cfg, seg,
                              cross_attn=cfg.is_encoder_decoder)
                for i, seg in enumerate(cfg.segments)
            ),
        }
        if not cfg.tie_embeddings:
            p["unembed"] = (jax.random.normal(
                ks[2], (cfg.d_model, cfg.vocab_size)) * 0.02).astype(
                    cfg.pdtype)
        if cfg.is_encoder_decoder:
            p["encoder"] = {
                "in_proj": (jax.random.normal(
                    ks[3], (AUDIO_FEAT_DIM, cfg.d_model)) * 0.05).astype(
                        cfg.pdtype),
                "segments": tuple(
                    _init_segment(jax.random.fold_in(ks[4], i), cfg, seg,
                                  cross_attn=False)
                    for i, seg in enumerate(cfg.encoder_segments)
                ),
                "final_norm": L.init_rmsnorm(cfg),
            }
        if cfg.num_image_tokens:
            p["img_proj"] = (jax.random.normal(
                ks[5], (IMAGE_FEAT_DIM, cfg.d_model)) * 0.05).astype(
                    cfg.pdtype)
        if cfg.mtp_depth:
            p["mtp"] = {
                "proj": (jax.random.normal(
                    ks[6], (2 * cfg.d_model, cfg.d_model)) * 0.02).astype(
                        cfg.pdtype),
                "block": _init_block(ks[7], cfg,
                                     Block("attn", "dense"), False),
                "norm": L.init_rmsnorm(cfg),
            }
        return p

    # ------------------------------------------------------------- embedding
    def _embed_inputs(self, params: Params, batch: Dict[str, jnp.ndarray]
                      ) -> Tuple[jnp.ndarray, int]:
        """Token (+modality stub) embedding. Returns (x, n_prefix) where
        n_prefix = number of non-text positions prepended (vlm)."""
        cfg = self.cfg
        x = params["embed"].astype(cfg.cdtype)[batch["tokens"]]
        n_prefix = 0
        if cfg.num_image_tokens and "image_feats" in batch:
            img = jnp.einsum("bnf,fd->bnd",
                             batch["image_feats"].astype(cfg.cdtype),
                             params["img_proj"].astype(cfg.cdtype))
            x = jnp.concatenate([img, x], axis=1)
            n_prefix = img.shape[1]
        return constrain_batch(x), n_prefix

    def _encode(self, params: Params, frames: jnp.ndarray) -> jnp.ndarray:
        """Whisper-style encoder over stub frame features (B,F,feat)."""
        cfg = self.cfg
        enc = params["encoder"]
        x = jnp.einsum("bfe,ed->bfd", frames.astype(cfg.cdtype),
                       enc["in_proj"].astype(cfg.cdtype))
        x = x + L.sinusoidal_positions(x.shape[1], cfg.d_model).astype(
            cfg.cdtype)
        x = constrain_batch(x)
        positions = jnp.arange(x.shape[1])[None, :]
        for seg, ps in zip(cfg.encoder_segments, enc["segments"]):
            x, _, _, _ = _run_segment(ps, cfg, seg, x, positions,
                                      mask=None, mask_kind="full")
        return L.rmsnorm(enc["final_norm"], cfg, x)

    def _head(self, params: Params, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        x = L.rmsnorm(params["final_norm"], cfg, x)
        w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        logits = jnp.einsum("bsd,dv->bsv", x,
                            w.astype(cfg.cdtype)).astype(jnp.float32)
        # keep logits vocab-sharded over `model`; the CE formulation in
        # `_cross_entropy` never gathers them.
        return constrain_batch(logits, (None, "model"))

    # --------------------------------------------------------------- forward
    def forward(self, params: Params, batch: Dict[str, jnp.ndarray]
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Training/scoring forward. Returns (logits(B,S,V) fp32, aux)."""
        cfg = self.cfg
        x, n_prefix = self._embed_inputs(params, batch)
        s = x.shape[1]
        positions = jnp.arange(s)[None, :]
        mask = None   # structural "causal" kind; never materialized
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = self._encode(params, batch["frames"])
        aux_total = jnp.zeros((), jnp.float32)
        for seg, ps in zip(cfg.segments, params["segments"]):
            x, aux, _, _ = _run_segment(ps, cfg, seg, x, positions, mask,
                                        "causal", enc_out=enc_out)
            aux_total = aux_total + aux
        logits = self._head(params, x)
        if n_prefix:
            logits = logits[:, n_prefix:]
            x = x[:, n_prefix:]
        if cfg.mtp_depth and batch.get("enable_mtp", True) is not False:
            aux_total = aux_total + self._mtp_loss(params, x, batch["tokens"])
        return logits, aux_total

    def _mtp_loss(self, params: Params, h: jnp.ndarray,
                  tokens: jnp.ndarray) -> jnp.ndarray:
        """DeepSeek-V3 multi-token prediction (depth 1): from h_i and
        emb(t_{i+1}) predict t_{i+2}; weighted auxiliary CE."""
        cfg = self.cfg
        if tokens.shape[1] < 3:
            return jnp.zeros((), jnp.float32)
        emb_next = params["embed"].astype(cfg.cdtype)[tokens[:, 1:]]
        hcat = jnp.concatenate([h[:, :-1], emb_next], axis=-1)
        x = jnp.einsum("bsd,de->bse", hcat,
                       params["mtp"]["proj"].astype(cfg.cdtype))
        s = x.shape[1]
        positions = jnp.arange(s)[None, :]
        x, _, _, _ = _apply_block(params["mtp"]["block"], cfg,
                                  Block("attn", "dense"), x, positions, None,
                                  "causal", None, None, None, None)
        x = L.rmsnorm(params["mtp"]["norm"], cfg, x)
        w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        logits = jnp.einsum("bsd,dv->bsv", x,
                            w.astype(cfg.cdtype)).astype(jnp.float32)
        targets = tokens[:, 2:]
        ce = _cross_entropy(logits[:, :-1], targets).mean()
        return 0.1 * ce

    def loss(self, params: Params, batch: Dict[str, jnp.ndarray]
             ) -> jnp.ndarray:
        logits, aux = self.forward(params, batch)
        tokens = batch["tokens"]
        ce = _cross_entropy(logits[:, :-1], tokens[:, 1:])
        if "loss_mask" in batch:
            m = batch["loss_mask"][:, 1:].astype(jnp.float32)
            ce = (ce * m).sum() / jnp.clip(m.sum(), 1.0)
        else:
            ce = ce.mean()
        return ce + aux

    # --------------------------------------------------------------- serving
    def prefill(self, params: Params, batch: Dict[str, jnp.ndarray],
                smax: int) -> Tuple[jnp.ndarray, Any]:
        """Process the full prompt; returns (last-position logits, cache)."""
        cfg = self.cfg
        x, n_prefix = self._embed_inputs(params, batch)
        s = x.shape[1]
        positions = jnp.arange(s)[None, :]
        mask = None   # structural "causal" kind; never materialized
        enc_out = None
        cache, cross = init_cache(cfg, x.shape[0], smax)
        if cfg.is_encoder_decoder:
            enc_out = self._encode(params, batch["frames"])
        new_cache = []
        new_cross = []
        for i, (seg, ps) in enumerate(zip(cfg.segments, params["segments"])):
            cs = cross[i] if cross is not None else None
            x, _, nc, ncross = _run_segment(
                ps, cfg, seg, x, positions, mask, "causal",
                cache_stack=cache[i], enc_out=enc_out, cross_stack=cs)
            new_cache.append(nc)
            new_cross.append(ncross)
        logits = self._head(params, x[:, -1:])
        cross_out = tuple(new_cross) if cross is not None else None
        return logits, (tuple(new_cache), cross_out)

    def decode_step(self, params: Params, token: jnp.ndarray, pos,
                    cache_state) -> Tuple[jnp.ndarray, Any]:
        """One decode step. token: (B,1) int32; pos: scalar int32 (current
        sequence position, 0-based). Returns (logits (B,1,V), new cache)."""
        cfg = self.cfg
        cache, cross = cache_state
        x = params["embed"].astype(cfg.cdtype)[token]
        positions = jnp.full((1, 1), pos, jnp.int32)
        new_cache = []
        for i, (seg, ps) in enumerate(zip(cfg.segments, params["segments"])):
            cs = cross[i] if cross is not None else None
            x, _, nc, _ = _run_segment(
                ps, cfg, seg, x, positions, mask=None, mask_kind="decode",
                cache_stack=cache[i], cache_pos=pos, cross_stack=cs)
            new_cache.append(nc)
        logits = self._head(params, x)
        return logits, (tuple(new_cache), cross)


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
