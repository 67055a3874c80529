"""Stage runtime: one generative model served by executor replicas.

A :class:`StageRuntime` is the stage fn a
:class:`~repro.serving.executor.PipelineExecutor` calls with a list of
request payloads (``(seq_len,)`` int32 prompts). It answers each with
the ``GEN_TOKENS`` tokens that greedy decoding produces: prefill the
prompt (``Model.prefill``), then ``Model.decode_step`` through the KV
cache, all in one jitted program per batch bucket.

* Params are placed once on each of the given devices and stay there.
* Every power-of-two batch bucket up to ``max_batch`` is compiled ahead
  of time, for every device, when the runtime is built, so serving never
  compiles. A partial batch is padded with zero prompts up to its bucket;
  the prompt length and the cache length are checked or rounded against
  the Pallas kernels' tiling (flash: Sq <= 128 or a multiple of 128;
  decode: Smax <= 512 or a multiple of 512).
* The host's steps of a call (``runtime.pad``, ``runtime.put``,
  ``runtime.launch``, ``runtime.fetch``) and the program's phases
  (``prefill``, ``decode``) are named in a profiler trace
  (:mod:`repro.serving.spans`).
* Each executor replica is a worker thread. The first batch a thread
  serves binds it to the next device, round robin, so ``replicas=4``
  over four devices puts one replica on each; ``batches`` counts the
  batches each device served.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.models import build_model
from repro.models.config import ArchConfig
from repro.serving.spans import span

REPO_ROOT = Path(__file__).resolve().parents[3]
GEN_TOKENS = 8      # greedy tokens generated per request


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself). Otherwise the cache lives at ``.jax_cache/`` in the
    checkout: a fixed path, because the path is part of the cache key.
    Entry points call this; importing the module changes nothing.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _flash_len(n: int) -> int:
    """Shortest sequence >= n the flash kernel tiles."""
    return n if n <= 128 else _round_up(n, 128)


def _cache_len(n: int) -> int:
    """Shortest cache >= n slots the decode kernel tiles."""
    return n if n <= 512 else _round_up(n, 512)


class StageRuntime:
    """Greedy generation for one model, on one or more devices.

    Args:
      cfg: the architecture, at the widths and dtypes it registers.
      devices: the jax devices replicas run on (params go on each).
      seq_len: prompt length of every payload.
      max_batch: largest batch served; buckets are 1, 2, 4, ... up to
        the first power of two >= max_batch.

    The weights are random, drawn from PRNG key 0.
    """

    def __init__(self, cfg: ArchConfig, devices: Sequence, seq_len: int,
                 max_batch: int):
        if _flash_len(seq_len) != seq_len:
            raise ValueError(f"seq_len={seq_len} does not tile the flash "
                             f"kernel (<= 128 or a multiple of 128)")
        self.cfg = cfg
        self.model = build_model(cfg)
        self.devices = list(devices)
        self.seq_len = seq_len
        self.smax = _cache_len(seq_len + GEN_TOKENS)
        self.buckets: Tuple[int, ...] = tuple(
            1 << i for i in range(max(max_batch - 1, 0).bit_length() + 1))
        self.max_batch = self.buckets[-1]
        with jax.default_device(self.devices[0]):
            params = jax.jit(self.model.init)(jax.random.PRNGKey(0))
        self.params = [jax.device_put(params, d) for d in self.devices]
        generate = jax.jit(self._generate_body)
        self._compiled = {}
        for di, dev in enumerate(self.devices):
            for b in self.buckets:
                toks = jax.ShapeDtypeStruct(
                    (b, seq_len), jnp.int32,
                    sharding=SingleDeviceSharding(dev))
                self._compiled[(di, b)] = generate.lower(
                    self.params[di], toks).compile()
        self._forward = jax.jit(
            lambda p, t: self.model.forward(p, {"tokens": t})[0])
        self._lock = threading.Lock()
        self._local = threading.local()
        self._next_device = 0                     # guarded-by: _lock
        self.batches = [0] * len(self.devices)    # guarded-by: _lock

    # -- the jitted body ---------------------------------------------------
    def _generate_body(self, params, tokens):
        """(B, seq_len) prompts -> (tokens (B, G) int32, logits (B, G, V)):
        token i is the argmax of logits i; logits 0 come from the prefill,
        the rest from one cached decode step each."""
        model = self.model
        with jax.named_scope("prefill"):
            logits, cache = model.prefill(params, {"tokens": tokens},
                                          self.smax)
            first = logits[:, -1]
            init = (jnp.argmax(first, axis=-1).astype(jnp.int32), cache)

        def step(carry, pos):
            tok, cache = carry
            lg, cache = model.decode_step(params, tok[:, None], pos, cache)
            lg = lg[:, -1]
            return (jnp.argmax(lg, axis=-1).astype(jnp.int32), cache), lg

        with jax.named_scope("decode"):
            _, rest = jax.lax.scan(
                step, init, self.seq_len + jnp.arange(GEN_TOKENS - 1))
            logits = jnp.concatenate([first[:, None], rest.swapaxes(0, 1)],
                                     axis=1)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits

    # -- calls -------------------------------------------------------------
    def compiled(self, batch: int, device: int = 0):
        """The ahead-of-time compiled program of one bucket."""
        return self._compiled[(device, batch)]

    def generate(self, tokens: np.ndarray, device: int = 0):
        """Run one warmed bucket: ``tokens`` (B, seq_len) with B a bucket.
        Returns device arrays (tokens (B, G), logits (B, G, V))."""
        fn = self._compiled[(device, tokens.shape[0])]
        with span("runtime.put"):
            toks = jax.device_put(np.asarray(tokens, np.int32),
                                  self.devices[device])
        with span("runtime.launch", device=device):
            return fn(self.params[device], toks)

    def profile_batch(self, batch: int) -> None:
        """One synchronous bucket-``batch`` call on device 0 (the
        ``run_batch`` that :func:`repro.core.profiler
        .profile_model_measured` times)."""
        toks = np.zeros((batch, self.seq_len), np.int32)
        jax.block_until_ready(self.generate(toks))

    def teacher_forced(self, tokens: np.ndarray, generated: np.ndarray,
                       device: int = 0) -> jnp.ndarray:
        """Logits of one causal ``Model.forward`` over each prompt plus
        its generated tokens, at the G positions that predicted them —
        the reference the cached generation must match. The sequence is
        zero-padded at the end to a length the flash kernel tiles."""
        b, s, g = tokens.shape[0], self.seq_len, GEN_TOKENS
        seq = np.zeros((b, _flash_len(s + g - 1)), np.int32)
        seq[:, :s] = tokens
        seq[:, s:s + g - 1] = np.asarray(generated)[:, :g - 1]
        logits = self._forward(self.params[device],
                               jax.device_put(seq, self.devices[device]))
        return logits[:, s - 1:s + g - 1]

    def _replica_device(self) -> int:
        dev = getattr(self._local, "device", None)
        if dev is None:
            with self._lock:
                dev = self._next_device % len(self.devices)
                self._next_device += 1
            self._local.device = dev
        return dev

    def __call__(self, payloads: List[np.ndarray]) -> List[np.ndarray]:
        """Executor stage fn: one (GEN_TOKENS,) int32 array per prompt."""
        n = len(payloads)
        if n > self.max_batch:
            raise ValueError(f"batch of {n} exceeds the largest warmed "
                             f"bucket {self.max_batch}")
        bucket = next(b for b in self.buckets if b >= n)
        with span("runtime.pad", bucket=bucket, rows=n):
            toks = np.zeros((bucket, self.seq_len), np.int32)
            toks[:n] = np.stack(payloads)
        dev = self._replica_device()
        out, _ = self.generate(toks, dev)
        with span("runtime.fetch"):
            out = np.asarray(out)
        with self._lock:
            self.batches[dev] += 1
        return list(out[:n])
