"""The dense family: a decoder-only transformer of RMSNorm, rotary
positions (the rotate-half form), causal multi-head attention with
grouped key/value heads, and a SwiGLU (``hidden_act: silu``) or
tanh-GELU MLP, with no biases and no attention window.

A family file gives the harness everything that depends on a model's
shape (``bench.spec.family`` finds it by the configuration file's
``family`` key):

* :func:`build_arch`: the registry's model cut as the file says, checked
  against the file;
* :func:`init_rule`: how each leaf's seeded weights are drawn
  (:mod:`bench.weights`);
* :func:`logits` and :data:`MODES`: the plain reference and its
  controls;
* :func:`request_flops` and :func:`kernel_calls`: operations and bytes
  from shapes alone.

The reference is written out in ``jax.numpy`` from the configuration
file alone. No kernels, no cache, no batching tricks: the whole sequence
is run at once, one layer at a time, with each layer's weights made from
the seed just before it is used (:mod:`bench.weights`), so the reference
fits beside nothing else and imports nothing of the program.

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

Operations and bytes count what the algorithm needs, from the
configuration file's sizes, and never read the program's own counters
(``flops_per_token``): a change to the program cannot move the
yardstick.

* A generate call is the prefill of ``prompt`` tokens (logits at the
  last position only) and ``gen - 1`` cached decode steps, each giving
  one position's logits: ``gen`` greedy tokens per request.
* Matrix products count 2 operations per multiply-add. Causal prefill
  attention counts the ``S (S + 1) / 2`` query-key pairs it needs; a
  decode step at position ``p`` attends ``p + 1`` keys.
* Kernel bytes count each operand the kernel needs read once and its
  output written once, at the compute dtype's width.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import leaf


def build_arch(config: Dict[str, Any], registry: Callable):
    """The registry's model at the file's depth and RMSNorm epsilon;
    every other size in the file has to be what the registry serves."""
    # the system under test comes from the program; the reference below
    # imports nothing of it
    from repro.models.config import dense_segments

    arch = dataclasses.replace(
        registry(config["registry_id"]),
        segments=dense_segments(int(config["num_hidden_layers"])),
        norm_eps=float(config["rms_norm_eps"]))
    want = {"d_model": config["hidden_size"],
            "num_heads": config["num_attention_heads"],
            "num_kv_heads": config["num_key_value_heads"],
            "d_ff": config["intermediate_size"],
            "vocab_size": config["vocab_size"],
            "act": "swiglu" if config["hidden_act"] == "silu" else "gelu",
            "rope_theta": config["rope_theta"],
            "param_dtype": config["param_dtype"],
            "compute_dtype": config["compute_dtype"],
            "tie_embeddings": config["tie_word_embeddings"]}
    got = {k: getattr(arch, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"registry {config['registry_id']!r} differs from "
                         f"the configuration file (registry, file): {bad}")
    if arch.family != "dense" or arch.qkv_bias or arch.sliding_window:
        raise ValueError("the reference covers dense models without "
                         "biases or windows only")
    return arch


# the input-side matrices, each drawn with fan-in its first axis
MATRICES = ("wq", "wk", "wv", "wg", "wu", "wd")


def init_rule(name: str, shape: Tuple[int, ...]) -> Tuple:
    """How leaf `name` (one layer's `shape`) is drawn: the embedding and
    the head small, norm gains near 1, every matrix by its fan-in (the
    attention output's over its heads and head size)."""
    last = name.rsplit("/", 1)[-1]
    if last in ("embed", "unembed"):
        return ("embed",)
    if last == "scale":
        return ("scale",)
    if last == "wo":
        return ("fan_in", (0, 1))
    if last in MATRICES:
        return ("fan_in", (0,))
    raise ValueError(f"the dense family draws no leaf {name!r} {shape}")


# -- the plain reference ------------------------------------------------------

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
    return {n: leaf(key, LAYER + n, layer, shape, init_rule)
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
    table = _weight(leaf(key, "embed", 0, (a.vocab, a.d), init_rule),
                    "embed", mode)
    return table[tokens]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _head(key, x, a: Arch, mode: str):
    scale = leaf(key, "final_norm/scale", 0, (a.d,), init_rule)
    w = _weight(leaf(key, "unembed", 0, (a.d, a.vocab), init_rule),
                "unembed", mode)
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


# -- operations and bytes -----------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Dims:
    d: int          # hidden size
    heads: int
    kv: int
    hd: int         # head size
    ff: int         # MLP width
    vocab: int
    layers: int
    gated: bool     # SwiGLU (three MLP matrices) or GELU (two)
    elem: int       # bytes per element of the compute dtype


def dims(config: Dict[str, Any]) -> Dims:
    heads = int(config["num_attention_heads"])
    return Dims(d=int(config["hidden_size"]), heads=heads,
                kv=int(config["num_key_value_heads"]),
                hd=int(config["hidden_size"]) // heads,
                ff=int(config["intermediate_size"]),
                vocab=int(config["vocab_size"]),
                layers=int(config["num_hidden_layers"]),
                gated=config["hidden_act"] == "silu",
                elem=np.dtype(config["compute_dtype"]).itemsize)


def layer_matmul_params(m: Dims) -> int:
    attn = 2 * m.d * m.heads * m.hd + 2 * m.d * m.kv * m.hd   # q, o; k, v
    return attn + (3 if m.gated else 2) * m.d * m.ff


def request_flops(config: Dict[str, Any], prompt: int, gen: int) -> float:
    """Operations one request needs: prefill, gen - 1 decode steps, and
    ``gen`` rows of logits."""
    m = dims(config)
    tokens = prompt + gen - 1
    dense = 2.0 * layer_matmul_params(m) * m.layers * tokens
    head = 2.0 * m.d * m.vocab * gen
    pairs = prompt * (prompt + 1) / 2 + sum(
        p + 1 for p in range(prompt, prompt + gen - 1))
    attn = 2.0 * 2.0 * m.heads * m.hd * pairs * m.layers   # QK^T and PV
    return dense + head + attn


def flash_call(m: Dims, batch: int, seq: int) -> Tuple[float, float]:
    """(operations, bytes) of one causal flash call over `batch` rows."""
    flops = 4.0 * batch * m.heads * m.hd * seq * (seq + 1) / 2
    nbytes = m.elem * batch * seq * m.hd * (2 * m.heads + 2 * m.kv)
    return flops, float(nbytes)


def decode_call(m: Dims, batch: int, valid: int) -> Tuple[float, float]:
    """(operations, bytes) of one decode-attention call over `valid`
    cached keys for each of `batch` rows."""
    flops = 4.0 * batch * m.heads * m.hd * valid
    nbytes = m.elem * batch * m.hd * (2 * m.heads + 2 * m.kv * valid)
    return flops, float(nbytes)


def kernel_calls(config: Dict[str, Any], bucket: int, prompt: int,
                 gen: int):
    """Yield ``(kernel, flops, bytes)`` for every attention kernel call
    of one generate over a `bucket`-row batch (padding rows included:
    the kernel does their work too)."""
    m = dims(config)
    for _ in range(m.layers):
        yield ("flash_attention",) + flash_call(m, bucket, prompt)
    for p in range(prompt, prompt + gen - 1):
        for _ in range(m.layers):
            yield ("decode_attention",) + decode_call(m, bucket, p + 1)
