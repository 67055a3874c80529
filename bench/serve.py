"""The system under test, built as a cell's data fixes it.

:func:`seeded_runtime` is the program's own ``StageRuntime``, built as
it builds itself, with its weights then replaced by the benchmark's
(:func:`bench.weights.make_params`): the compiled programs take the
weights as an argument, so serving, padding, bucketing, the jitted
generate body and device binding are the program's own.

:class:`StageRecorder` wraps the runtime as the executor's stage fn. It
records each call's host span and the requests it served, keeps the
logits the timed path computed for the requests the check will sample,
and writes a ``TraceAnnotation`` per call so the trace reduction can put
the host's work on the device's clock.
"""

from __future__ import annotations

import gc
import threading
from typing import Callable, Dict, Iterable, List, Sequence

import jax
import numpy as np

from repro.serving.runtime import StageRuntime


def seeded_runtime(cfg, devices: Sequence, seq_len: int, max_batch: int,
                   make_params: Callable[[], object]) -> StageRuntime:
    """The program's ``StageRuntime`` serving the weights `make_params`
    makes on ``devices[0]``; its own weights are freed first, so two
    copies never share a chip."""
    rt = StageRuntime(cfg, devices, seq_len, max_batch)
    rt.params = None
    gc.collect()
    with jax.default_device(devices[0]):
        params = make_params()
    rt.params = [jax.device_put(params, d) for d in rt.devices]
    del params
    return rt


def warm(rt: StageRuntime) -> None:
    """Run every compiled bucket once on every device."""
    for di in range(len(rt.devices)):
        for b in rt.buckets:
            toks = np.zeros((b, rt.seq_len), np.int32)
            jax.block_until_ready(rt.generate(toks, di))


class StageRecorder:
    """Executor stage fn around a runtime: records every call.

    ``calls`` holds one ``(device, t_start, t_end, rows)`` per call on
    the executor's clock; ``started[i]`` is the time request ``i``'s
    batch started. ``index`` maps a payload's ``id`` to its request.
    For each request in ``keep``, ``kept[i]`` is ``(logits, row)``: the
    device array of logits its batch's program returned, left on the
    device until the window has closed, and its row there.
    """

    def __init__(self, fn: Callable[[List[np.ndarray]], List[np.ndarray]],
                 runtime: StageRuntime, index: Dict[int, int],
                 keep: Iterable[int] = ()):
        self.fn = fn
        self.runtime = runtime
        self.index = index
        self.keep = frozenset(int(i) for i in keep)
        self.kept: Dict[int, tuple] = {}
        self.clock: Callable[[], float] = lambda: 0.0
        self.started = np.full(len(index), np.nan)
        self.calls: List[tuple] = []      # guarded-by: _lock
        self._lock = threading.Lock()
        self._thread = threading.local()
        generate = runtime.generate

        def recording_generate(tokens, device=0):
            out = generate(tokens, device)
            self._thread.out = out
            return out

        runtime.generate = recording_generate

    def _warm_thread(self) -> None:
        """Run every bucket once from the calling (replica) thread, on its
        device: the first use of a bucket from a thread that has not run
        it yet must not fall into the window. Runs in the thread's first
        call, which the warm-up segment makes."""
        if getattr(self._thread, "warm", False):
            return
        dev = self.runtime._replica_device()
        for b in self.runtime.buckets:
            toks = np.zeros((b, self.runtime.seq_len), np.int32)
            jax.block_until_ready(self.runtime.generate(toks, dev))
        self._thread.warm = True

    def __call__(self, payloads: List[np.ndarray]) -> List[np.ndarray]:
        self._warm_thread()
        with self._lock:
            k = len(self.calls)
            self.calls.append(None)
        self._thread.out = None
        t0 = self.clock()
        with jax.profiler.TraceAnnotation(f"stage_call#{k}"):
            out = self.fn(payloads)
        t1 = self.clock()
        dev = getattr(self.runtime._local, "device", 0)
        logits = self._thread.out[1] if self._thread.out else None
        for row, p in enumerate(payloads):
            i = self.index[id(p)]
            self.started[i] = t0
            if i in self.keep and logits is not None:
                self.kept[i] = (logits, row)
        with self._lock:
            self.calls[k] = (dev, t0, t1, len(payloads))
        return out

    def kept_logits(self, requests: Sequence[int], shape) -> np.ndarray:
        """(len(requests), *shape) float32: the kept logits of each
        request; NaN where none were kept, or its row is past its
        batch's end."""
        out = np.full((len(requests), *shape), np.nan, np.float32)
        host: Dict[int, np.ndarray] = {}
        for j, i in enumerate(requests):
            if i not in self.kept:
                continue
            arr, row = self.kept[i]
            if id(arr) not in host:
                host[id(arr)] = np.asarray(arr)
            if row < host[id(arr)].shape[0]:
                out[j] = host[id(arr)][row]
        return out
