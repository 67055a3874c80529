"""Operations and bytes of the served program, from shapes alone.

These count what the algorithm needs, from the configuration file's
sizes, and never read the program's own counters (``flops_per_token``):
a change to the program cannot move the yardstick.

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
from typing import Any, Dict, Tuple

import numpy as np


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


def request_flops(m: Dims, prompt: int, gen: int) -> float:
    """Operations one request needs: prefill, gen - 1 decode steps, and
    ``gen`` rows of logits."""
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


def generate_kernel_calls(m: Dims, bucket: int, prompt: int, gen: int):
    """Yield ``(kernel, flops, bytes)`` for every attention kernel call
    of one generate over a `bucket`-row batch (padding rows included:
    the kernel does their work too)."""
    for _ in range(m.layers):
        yield ("flash_attention",) + flash_call(m, bucket, prompt)
    for p in range(prompt, prompt + gen - 1):
        for _ in range(m.layers):
            yield ("decode_attention",) + decode_call(m, bucket, p + 1)


def roofline_s(flops: float, nbytes: float, peak: Dict[str, Any]) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
