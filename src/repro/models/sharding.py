"""FSDP x TP partition specs for the model zoo.

Mesh axes: ``("data", "model")`` single pod, ``("pod", "data", "model")``
multi-pod. Parameters are fully sharded (FSDP over the data axes + tensor
parallelism over `model` on the layer's natural parallel dimension:
attention heads, FFN hidden, experts, vocab). Divisibility is validated
per leaf; any non-divisible dim falls back to replication on that axis so
odd vocabularies (whisper's 51865) and tiny smoke configs still lower.

Rules are path-based (regex on the flattened param path, e.g.
``['segments'][0][0]['core']['wq']``); stacked segment leaves carry a
leading ``repeat`` axis which is always replicated (specs align to the
TRAILING dims, tolerating 0 or 1 leading axes).
"""

from __future__ import annotations

import re
from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (path regex, spec). "fsdp" => mesh data axes; "model" => TP axis.
_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    # embeddings / heads / modality projectors
    (r"\['embed'\]$", ("model", "fsdp")),
    (r"\['unembed'\]$", ("fsdp", "model")),
    (r"\['img_proj'\]$", (None, "fsdp")),
    (r"\['encoder'\]\['in_proj'\]$", (None, "fsdp")),
    # attention (3-D head-split weights) + biases
    (r"\['(?:core|cross)'\]\['wq'\]$", ("fsdp", "model", None)),
    (r"\['(?:core|cross)'\]\['w[kv]'\]$", ("fsdp", "model", None)),
    (r"\['(?:core|cross)'\]\['wo'\]$", ("model", None, "fsdp")),
    (r"\['b[qkv]'\]$", ("model", None)),
    # MLA
    (r"\['wq_a'\]$", ("fsdp", None)),
    (r"\['wq_b'\]$", ("fsdp", "model", None)),
    (r"\['wkv_a'\]$", ("fsdp", None)),
    (r"\['wkv_b_[kv]'\]$", (None, "model", None)),
    # MoE router
    (r"\['router'\]$", ("fsdp", None)),
    # mamba
    (r"\['core'\]\['in_proj'\]$", ("fsdp", "model")),
    (r"\['conv_w'\]$", (None, "model")),
    (r"\['w_bc'\]$", ("model", None)),
    (r"\['(?:w_dt|b_dt|d_skip)'\]$", ("model",)),
    (r"\['a_log'\]$", ("model", None)),
    (r"\['out_proj'\]$", ("model", "fsdp")),
    # mlstm
    (r"\['up'\]$", ("fsdp", "model")),
    (r"\['m[qkv]'\]$", ("fsdp", "model")),
    (r"\['w_[if]'\]$", ("model", None)),
    (r"\['b_[if]'\]$", ("model",)),
    (r"\['down'\]$", ("model", "fsdp")),
    # slstm: REPLICATED. The sLSTM recurrence is a 4096-step sequential
    # scan; TP-sharding r_h puts one small all-reduce inside every
    # timestep (measured: t_collective 1.06 s/step on xlstm-125m
    # train_4k — the dominant term). The weights are d_model^2-sized
    # (2.4 MB at d=768): replicating them deletes the per-step
    # collectives entirely (§Perf iteration 10).
    (r"\['(?:w_x|r_h)'\]$", (None, None)),
    (r"\['core'\]\['bias'\]$", (None,)),
    (r"\['core'\]\['proj'\]$", (None, None)),
    # heads
    (r"\['mtp'\]\['proj'\]$", ("fsdp", None)),
)

# dense-vs-MoE FFN weights share names under ['ffn']/['shared']; the MoE
# variants are one rank higher ((E, D, F) with experts over `model`).
_FFN_RE = re.compile(r"\['(?:ffn|shared)'\]\['w([gud])'\]$")
_FFN_DENSE = {"g": ("fsdp", "model"), "u": ("fsdp", "model"),
              "d": ("model", "fsdp")}
_FFN_MOE = {"g": ("model", "fsdp", None), "u": ("model", "fsdp", None),
            "d": ("model", "fsdp", None)}


def _path_str(path) -> str:
    return "".join(str(p) for p in path)


def _axes(mesh: Mesh) -> Tuple[Sequence[str], str]:
    names = mesh.axis_names
    model = "model"
    fsdp = tuple(n for n in names if n != model)
    return fsdp, model


def _resolve(spec: Tuple[Optional[str], ...], shape: Tuple[int, ...],
             mesh: Mesh) -> P:
    """Align `spec` to the trailing dims of `shape` (0-1 leading repeat
    axes allowed) with per-dim divisibility fallbacks."""
    fsdp_axes, model_axis = _axes(mesh)
    fsdp_size = 1
    for a in fsdp_axes:
        fsdp_size *= mesh.shape[a]
    model_size = mesh.shape[model_axis]

    n_lead = len(shape) - len(spec)
    if n_lead not in (0, 1):
        return P()
    out: list = [None] * n_lead
    for dim_size, s in zip(shape[n_lead:], spec):
        if s == "fsdp" and dim_size % fsdp_size == 0:
            out.append(fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0])
        elif s == "model" and dim_size % model_size == 0:
            out.append(model_axis)
        else:
            out.append(None)
    return P(*out)


def constrain_batch(x, extra=()):
    """Pin the leading (batch) dim of an activation to the ambient mesh's
    data axes; no-op outside a mesh context.

    WHY: FSDP shards weights over the same mesh axes as the batch. In an
    unconstrained module XLA's sharding propagation may resolve the
    (batch over data) x (weight-contraction over data) conflict by
    REPLICATING activations instead of all-gathering weights — observed as
    full-batch f32[256,4096,8192] FFN activations on every device in the
    llama3.2-1b train_4k dry-run. An explicit constraint on the residual
    stream forces the ZeRO-3 resolution (gather weights, keep activations
    sharded).

    `extra` optionally pins trailing dims (e.g. ("model",) for a
    vocab-sharded logits tensor).
    """
    from jax.interpreters import pxla

    mesh = pxla.thread_resources.env.physical_mesh
    if mesh.empty or "model" not in mesh.axis_names:
        return x
    fsdp_axes, _ = _axes(mesh)
    size = 1
    for a in fsdp_axes:
        size *= mesh.shape[a]
    if x.ndim < 1 or size <= 1 or x.shape[0] % size != 0:
        return x
    first = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
    tail = list(extra) + [None] * (x.ndim - 1 - len(extra))
    for i, name in enumerate(tail):
        if name is not None and x.shape[1 + i] % mesh.shape[name] != 0:
            tail[i] = None
    return jax.lax.with_sharding_constraint(x, P(first, *tail))


def constrain_kv(x):
    """Pin a segment's head-major KV-cache stack (repeat, B, KV, S, hd) to
    the canonical cache sharding inside the decode/prefill computation;
    no-op outside a mesh context.

    Mirrors ``cache_pspec``: batch over data; KV heads over `model` when
    divisible, otherwise the SEQUENCE over `model`. Without this pin SPMD
    propagation inside the layer scan flips between seq-sharded (the
    cache argument) and head-sharded (what the attention einsum prefers),
    hitting XLA's "involuntary full rematerialization" path — a fully
    replicated cache copy per layer (observed on qwen2-72b decode_32k).
    """
    from jax.interpreters import pxla

    mesh = pxla.thread_resources.env.physical_mesh
    if mesh.empty or "model" not in mesh.axis_names or x.ndim != 5:
        return x
    fsdp_axes, model_axis = _axes(mesh)
    fsdp_size = 1
    for a in fsdp_axes:
        fsdp_size *= mesh.shape[a]
    model_size = mesh.shape[model_axis]
    _, b, kv, s, _ = x.shape
    spec = [None] * 5
    if fsdp_size > 1 and b % fsdp_size == 0:
        spec[1] = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
    if model_size > 1:
        if kv % model_size == 0:
            spec[2] = model_axis
        elif s % model_size == 0:
            spec[3] = model_axis
    return jax.lax.with_sharding_constraint(x, P(*spec))


def param_pspec(params: Any, mesh: Mesh) -> Any:
    def assign(path, leaf):
        ps = _path_str(path)
        shape = leaf.shape
        m = _FFN_RE.search(ps)
        if m:
            which = m.group(1)
            # stacked MoE: (repeat,E,D,F)=4; unstacked MoE: 3 with experts
            # -- distinguish dense (<=3 with last-2 dims) by trying MoE
            # spec first when rank allows a valid alignment
            for spec in ((_FFN_MOE[which],) if len(shape) >= 3 else ()) + \
                    (_FFN_DENSE[which],):
                n_lead = len(shape) - len(spec)
                if n_lead in (0, 1):
                    # rank-3 could be stacked-dense or unstacked-moe; the
                    # shared expert and dense MLP are (D,F)-shaped on the
                    # trailing dims, experts are (E,D,F). Stacked dense has
                    # (repeat, D, F): middle dim == d_model distinguishes.
                    if len(spec) == 3 and len(shape) == 3 and \
                            "shared" in ps:
                        continue  # shared expert is dense-shaped
                    return _resolve(spec, shape, mesh)
            return P()
        for pat, spec in _RULES:
            if re.search(pat, ps):
                return _resolve(spec, shape, mesh)
        return P()  # replicated (norm scales, small vectors)

    return jax.tree_util.tree_map_with_path(assign, params)


def param_sharding(params: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        param_pspec(params, mesh),
        is_leaf=lambda x: isinstance(x, P))


def batch_pspec(batch: Any, mesh: Mesh) -> Any:
    """Shard the batch dimension over the data axes when divisible."""
    fsdp_axes, _ = _axes(mesh)
    fsdp_size = 1
    for a in fsdp_axes:
        fsdp_size *= mesh.shape[a]

    def assign(leaf):
        if leaf.ndim >= 1 and leaf.shape[0] % fsdp_size == 0:
            first = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
            return P(first, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map(assign, batch)


def cache_pspec(cache: Any, mesh: Mesh, shard_seq: bool = False) -> Any:
    """Decode-cache specs for what ``init_cache`` returns, the pair
    (decoder cache, cross K/V or None): batch over data axes; KV heads /
    latent dim / state channels over model where divisible. With
    ``shard_seq`` (long_500k, batch=1) the cache *sequence* axis shards
    over the data axes instead — sequence-parallel attention over the
    long context.

    The decoder's dense K/V is head-major (rep,B,KV,S,hd); the cross
    K/V (rep,B,F,KV,hd) and MLA latents (rep,B,S,kr) put the sequence
    first."""
    fsdp_axes, model_axis = _axes(mesh)
    fsdp_size = 1
    for a in fsdp_axes:
        fsdp_size *= mesh.shape[a]
    model_size = mesh.shape[model_axis]
    data_axes = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]

    def assign(path, leaf):
        shape = leaf.shape
        ps = _path_str(path)
        spec: list = [None] * len(shape)
        # leading repeat axis replicated; dim 1 is batch
        if len(shape) >= 2 and shape[1] % fsdp_size == 0 and not shard_seq:
            spec[1] = data_axes
        if re.search(r"\['(?:k|v|k_rope|c_kv)'\]$", ps) and len(shape) >= 4:
            head_major = path[0].idx == 0 and re.search(r"\['[kv]'\]$", ps)
            seq, head = (3, 2) if head_major else (2, 3)
            if shard_seq and shape[seq] % fsdp_size == 0:
                spec[seq] = data_axes
            if shape[head] % model_size == 0:
                spec[head] = model_axis
            elif spec[seq] is None and shape[seq] % model_size == 0:
                # GQA caches whose KV heads don't divide the model axis
                # (qwen2 kv=8 on model=16: 1.37 TiB cache replicated
                # model-wise). Shard the SEQUENCE dim over `model`
                # instead — flash-decode style: each model shard holds a
                # context slice; softmax max/sum combine via the
                # reductions XLA already partializes.
                spec[seq] = model_axis
        elif re.search(r"\['(?:h|conv|C|n)'\]$", ps) and len(shape) >= 3:
            # ssm/xlstm states: channel dim over model
            ch_dim = 2 if not re.search(r"\['conv'\]$", ps) else 3
            if ch_dim < len(shape) and shape[ch_dim] % model_size == 0:
                spec[ch_dim] = model_axis
        return P(*spec)

    return jax.tree_util.tree_map_with_path(assign, cache)
