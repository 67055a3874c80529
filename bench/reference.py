"""Plain reference of the served models, for the check that decides
``correct``.

A decoder-only transformer written out in ``jax.numpy`` from the
configuration file alone: RMSNorm, rotary positions (the rotate-half
form), causal multi-head attention with grouped key/value heads, and a
SwiGLU (``hidden_act: silu``) or tanh-GELU MLP. No kernels, no cache, no
batching tricks: the whole sequence is run at once, one layer at a time,
with each layer's weights made from the seed just before it is used
(:mod:`bench.weights`), so the reference fits beside nothing else and
imports nothing of the program.

Three precisions, by ``mode``:

* ``float32``: the reference, as the configuration states it: float32
  weights and activations, every product at the file's
  ``matmul_precision`` (``default``: one bfloat16 pass with float32
  sums, as the TPU runs a float32 matmul by default; ``highest``);
* ``bfloat16``: weights, activations, products and the residual stream
  in bfloat16, the step below float32: the control;
* ``int8``: weights rounded to int8 with one scale per output channel
  (per row of the embedding) and every input of a linear layer rounded
  to int8 with one scale per token, as an int8 matrix unit computes
  them; the rest as ``float32``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import leaf

LAYER = "segments/0/0/"


class Arch(NamedTuple):
    precision: str
    d: int
    heads: int
    kv: int
    ff: int
    vocab: int
    layers: int
    gated: bool
    theta: float
    eps: float

    @property
    def hd(self) -> int:
        return self.d // self.heads


def arch(config: Dict[str, Any]) -> Arch:
    return Arch(config["matmul_precision"], int(config["hidden_size"]),
                int(config["num_attention_heads"]),
                int(config["num_key_value_heads"]),
                int(config["intermediate_size"]), int(config["vocab_size"]),
                int(config["num_hidden_layers"]),
                config["hidden_act"] == "silu", float(config["rope_theta"]),
                float(config["rms_norm_eps"]))


def _layer_shapes(a: Arch) -> Dict[str, tuple]:
    s = {"norm1/scale": (a.d,), "core/wq": (a.d, a.heads, a.hd),
         "core/wk": (a.d, a.kv, a.hd), "core/wv": (a.d, a.kv, a.hd),
         "core/wo": (a.heads, a.hd, a.d), "norm2/scale": (a.d,),
         "ffn/wu": (a.d, a.ff), "ffn/wd": (a.ff, a.d)}
    if a.gated:
        s["ffn/wg"] = (a.d, a.ff)
    return s


@functools.partial(jax.jit, static_argnums=(1,))
def _layer_weights(key, a: Arch, layer):
    return {n: leaf(key, LAYER + n, layer, shape)
            for n, shape in _layer_shapes(a).items()}


MODES = ("float32", "bfloat16", "int8")
PRECISIONS = {"default": jax.lax.Precision.DEFAULT,
              "highest": jax.lax.Precision.HIGHEST}
# axes each weight is reduced over for its int8 scale: its input axes
INT8_AXES = {"core/wq": (0,), "core/wk": (0,), "core/wv": (0,),
             "core/wo": (0, 1), "ffn/wg": (0,), "ffn/wu": (0,),
             "ffn/wd": (0,), "unembed": (0,), "embed": (1,)}


def _dtype(mode):
    return jnp.bfloat16 if mode == "bfloat16" else jnp.float32


def _prec(mode, a: Arch):
    if mode == "float32":
        return PRECISIONS[a.precision]
    return (jax.lax.Precision.DEFAULT if mode == "bfloat16"
            else jax.lax.Precision.HIGHEST)


def _int8(x, axes):
    """x rounded to int8 with one absmax scale per slice over `axes`."""
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _weight(w, name, mode):
    """A weight as `mode` computes with it."""
    if mode == "int8" and name in INT8_AXES:
        return _int8(w, INT8_AXES[name])
    return w.astype(_dtype(mode))


def _act(x, mode, axes=(-1,)):
    """A linear layer's input as `mode` computes with it."""
    return _int8(x, axes) if mode == "int8" else x


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * scale.astype(x.dtype)


def _rope(x, theta):
    """Rotate-half rotary embedding; x (B, S, H, hd), positions 0..S-1."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(x.shape[1])[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :hd // 2], xf[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _block(w, x, a: Arch, mode: str):
    p, dtype = _prec(mode, a), _dtype(mode)
    w = {n: _weight(v, n, mode) for n, v in w.items()}
    h = _act(_rms(x, w["norm1/scale"], a.eps), mode)
    q = _rope(jnp.einsum("bsd,dhk->bshk", h, w["core/wq"], precision=p),
              a.theta)
    k = _rope(jnp.einsum("bsd,dhk->bshk", h, w["core/wk"], precision=p),
              a.theta)
    v = jnp.einsum("bsd,dhk->bshk", h, w["core/wv"], precision=p)
    g = a.heads // a.kv
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhk,bthk->bhqt", q, k, precision=p,
                   preferred_element_type=jnp.float32) / np.sqrt(a.hd)
    n = x.shape[1]
    causal = np.tril(np.ones((n, n), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1).astype(dtype)
    o = jnp.einsum("bhqt,bthk->bqhk", pr, v, precision=p)
    x = x + jnp.einsum("bshk,hkd->bsd", _act(o, mode, (-2, -1)),
                       w["core/wo"], precision=p)
    h = _act(_rms(x, w["norm2/scale"], a.eps), mode)
    u = jnp.einsum("bsd,df->bsf", h, w["ffn/wu"], precision=p)
    if a.gated:
        gate = jnp.einsum("bsd,df->bsf", h, w["ffn/wg"], precision=p)
        u = jax.nn.silu(gate) * u
    else:
        u = _gelu_tanh(u)
    return x + jnp.einsum("bsf,fd->bsd", _act(u, mode), w["ffn/wd"],
                          precision=p)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _embed(key, tokens, a: Arch, mode: str):
    table = _weight(leaf(key, "embed", 0, (a.vocab, a.d)), "embed", mode)
    return table[tokens]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _head(key, x, a: Arch, mode: str):
    scale = leaf(key, "final_norm/scale", 0, (a.d,))
    w = _weight(leaf(key, "unembed", 0, (a.d, a.vocab)), "unembed", mode)
    h = _act(_rms(x, scale, a.eps), mode)
    return jnp.einsum("bsd,dv->bsv", h, w, precision=_prec(mode, a),
                      preferred_element_type=jnp.float32)


def logits(config: Dict[str, Any], key, seqs: np.ndarray, last: int,
           mode: str = "float32", rows: int = 64) -> np.ndarray:
    """Logits (N, last, V) at the final `last` positions of each of the
    (N, S) token sequences, in blocks of `rows` sequences."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    a = arch(config)
    out = []
    for i in range(0, seqs.shape[0], rows):
        x = _embed(key, jnp.asarray(seqs[i:i + rows], jnp.int32), a, mode)
        for layer in range(a.layers):
            x = _block(_layer_weights(key, a, layer), x, a, mode)
        out.append(np.asarray(_head(key, x[:, -last:], a, mode)))
    return np.concatenate(out)


def teacher_forced(prompts: np.ndarray, served: np.ndarray) -> np.ndarray:
    """Each prompt followed by all but the last served token: the
    sequence whose final ``G`` positions predicted the ``G`` served
    tokens."""
    return np.concatenate([prompts, served[:, :-1]], axis=1)


def widest_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """Largest amount by which a token's reference logit lies below the
    reference's best at its position. (N, G, V) and (N, G)."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, tokens[..., None].astype(np.int64),
                             axis=-1)[..., 0]
    return float((best - got).max())


def widest_logit_error(ref_logits: np.ndarray, logits: np.ndarray) -> float:
    """Largest relative distance, over positions, between a position's
    logits and the reference's: ``|l - r| / |r|`` over the vocabulary.
    (N, G, V) both; infinite where a value is not finite."""
    err = (np.linalg.norm(logits - ref_logits, axis=-1)
           / np.linalg.norm(ref_logits, axis=-1))
    return float(err.max()) if np.isfinite(err).all() else float("inf")
