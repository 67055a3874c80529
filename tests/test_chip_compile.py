"""Compile the main path's Pallas kernels, a full-width llama3.2-1b
prefill and a phi3-width generate program, for a TPU v5e that is
described, not attached.

Nothing runs: the TPU compiler refuses here what the chip would refuse
(untiled blocks, too much VMEM, unsupported ops) and each test asserts
the kernel survived into the compiled HLO as a ``tpu_custom_call``. The
topology is described inside a module fixture, never at import, so
every xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""

import dataclasses
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.rmsnorm import rmsnorm
from repro.models import build_model
from repro.models.config import dense_segments
from repro.serving.runtime import StageRuntime

LLAMA = get_arch("llama3.2-1b")
PHI3 = get_arch("phi3-mini-3.8b")
JAMBA = get_arch("jamba-1.5-large-398b")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # compiles for a described chip cannot be read back from the
    # persistent cache; keep it out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU lib
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _named_kernel(txt: str, name: str) -> bool:
    """A Mosaic kernel op in compiled HLO text named after its
    ``pallas_call(name=...)``, which the profiler trace shows."""
    return re.search(rf"%{name}(\.\d+)? = [^\n]*custom_call_target="
                     rf'"tpu_custom_call"', txt) is not None


def test_flash_causal_llama_widths(one_chip):
    h, kv, d = LLAMA.num_heads, LLAMA.num_kv_heads, LLAMA.resolved_head_dim
    txt = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        _sds((2, 1024, h, d), one_chip), _sds((2, 1024, kv, d), one_chip),
        _sds((2, 1024, kv, d), one_chip))
    assert _named_kernel(txt, "flash_attention")


def test_decode_llama_widths_smax_2048(one_chip):
    h, kv, d = LLAMA.num_heads, LLAMA.num_kv_heads, LLAMA.resolved_head_dim
    stack = (LLAMA.num_layers, 8, kv, 2048, d)     # head-major cache stack
    txt = _compiled_text(
        lambda q, k, v, n, layer: decode_attention(q, k, v, n, layer),
        _sds((8, 1, h, d), one_chip), _sds(stack, one_chip),
        _sds(stack, one_chip), _sds((), one_chip, jnp.int32),
        _sds((), one_chip, jnp.int32))
    assert _named_kernel(txt, "decode_attention")


@pytest.mark.parametrize("lead", [(8, 512), (3, 100)])
def test_rmsnorm_rows(one_chip, lead):
    txt = _compiled_text(rmsnorm, _sds(lead + (LLAMA.d_model,), one_chip),
                         _sds((LLAMA.d_model,), one_chip))
    assert _named_kernel(txt, "rmsnorm")


def test_mamba_scan_jamba_widths(one_chip):
    d_in = JAMBA.d_model * JAMBA.mamba_expand
    n = JAMBA.mamba_d_state
    assert (d_in, n) == (16384, 16)
    s = JAMBA.ssm_chunk
    txt = _compiled_text(
        lambda dt, x, b, c, a, h0: mamba_scan(dt, x, b, c, a, h0, chunk=s),
        _sds((1, s, d_in), one_chip), _sds((1, s, d_in), one_chip),
        _sds((1, s, n), one_chip), _sds((1, s, n), one_chip),
        _sds((d_in, n), one_chip), _sds((1, d_in, n), one_chip))
    assert "tpu_custom_call" in txt


def test_llama_full_width_prefill(one_chip, monkeypatch):
    # the model asks jax.default_backend(), which is the CPU here: steer
    # its kernel dispatch to the TPU branch for this compile
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    model = build_model(LLAMA)
    params = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, one_chip, a.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    compiled = jax.jit(
        lambda p, t: model.prefill(p, {"tokens": t}, 512)).lower(
            params, _sds((3, 100), one_chip, jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def _hlo_computations(txt: str):
    """Compiled HLO text -> {computation: [(name, op, dims, operands,
    callees)]}; dims is None for a tuple-shaped result."""
    comps, cur = {}, None
    for line in txt.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
            continue
        ins = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (?:\w+\[([\d,]*)\]"
                       r"(?:\{[^}]*\})?|\(.*?\)) ([\w\-]+)\((.*)", line)
        if cur is None or not ins:
            continue
        name, dims, op, rest = ins.groups()
        operands = re.findall(r"%([\w.\-]+)", rest.split(")", 1)[0])
        callees = re.findall(
            r"(?:calls|body|condition|to_apply)=%([\w.\-]+)", rest)
        for group in re.findall(r"branch_computations=\{([^}]*)\}", rest):
            callees += re.findall(r"%([\w.\-]+)", group)
        shape = None if dims is None else tuple(
            int(d) for d in dims.split(",") if d)
        cur.append((name, op, shape, operands, callees))
    return comps


def _reachable(comps, root: str):
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c not in seen and c in comps:
            seen.add(c)
            todo += [cal for ins in comps[c] for cal in ins[4]]
    return seen


def test_phi3_decode_keeps_the_kv_cache_in_place(one_chip, monkeypatch):
    """The generate program's decode loop (phi3 widths, 2 layers, B=16,
    Smax=136) moves no layer's K or V cache: no copy, transpose or
    (dynamic) slice whose result spans the cache's Smax axis and holds
    B*KV*Smax*hd elements or more, and no dynamic-update-slice writes an
    update that large. The decode kernel reads the cache stack where it
    lies, as a ``tpu_custom_call``."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    b, seq, smax = 16, 128, 136
    cfg = dataclasses.replace(PHI3, segments=dense_segments(2))
    model = build_model(cfg)
    params = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, one_chip, a.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    runtime = types.SimpleNamespace(model=model, seq_len=seq, smax=smax)
    txt = _compiled_text(
        lambda p, t: StageRuntime._generate_body(runtime, p, t),
        params, _sds((b, seq), one_chip, jnp.int32))
    assert _named_kernel(txt, "decode_attention")

    comps = _hlo_computations(txt)
    kernel_in = {c for c, ins in comps.items()
                 if any(i[0].startswith("decode_attention") for i in ins)}
    # the decode loop: the outermost while whose body reaches the kernel
    loops = [_reachable(comps, i[4][0]) | _reachable(comps, i[4][1])
             for ins in comps.values() for i in ins
             if i[1] == "while" and len(i[4]) == 2]
    decode = max((r for r in loops if r & kernel_in), key=len)
    layer = b * cfg.num_kv_heads * smax * cfg.resolved_head_dim

    def cache_sized(shape):
        return (shape is not None and smax in shape
                and int(np.prod(shape)) >= layer)

    moves = {"copy", "copy-start", "copy-done", "transpose", "slice",
             "dynamic-slice", "slice-start", "slice-done"}
    found = []
    for c in decode:
        shapes = {i[0]: i[2] for i in comps[c]}
        for name, op, shape, operands, _ in comps[c]:
            if op in moves and cache_sized(shape):
                found.append((c, name, op, shape))
            if op == "dynamic-update-slice" and cache_sized(
                    shapes.get(operands[1])):
                found.append((c, name, op, shapes[operands[1]]))
    assert not found, found
