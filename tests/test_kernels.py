"""Pallas kernels: interpret=True sweeps vs the pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm

TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
       jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ------------------------------------------------------------- flash attention

@pytest.mark.parametrize("b,s,h,kv,d", [
    (1, 128, 4, 4, 64),     # MHA
    (2, 256, 8, 2, 64),     # GQA 4:1
    (1, 512, 4, 1, 128),    # MQA, larger d
    (2, 128, 2, 2, 32),     # small head_dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_causal_sweep(b, s, h, kv, d, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(k1, (b, s, h, d), dtype)
    k = _rand(k2, (b, s, kv, d), dtype)
    v = _rand(k3, (b, s, kv, d), dtype)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               **TOL[dtype])


def test_flash_non_causal():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(k1, (2, 128, 4, 64), jnp.float32)
    k = _rand(k2, (2, 256, 4, 64), jnp.float32)
    v = _rand(k3, (2, 256, 4, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)


def test_flash_cross_lengths_causal_offset():
    """Sq < Sk: causal diagonal offset (chunked prefill pattern)."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(k1, (1, 128, 4, 64), jnp.float32)
    k = _rand(k2, (1, 384, 4, 64), jnp.float32)
    v = _rand(k3, (1, 384, 4, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [64, 128, 200])
def test_flash_sliding_window(window):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(k1, (1, 256, 4, 64), jnp.float32)
    k = _rand(k2, (1, 256, 2, 64), jnp.float32)
    v = _rand(k3, (1, 256, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window,
                          interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)


def test_flash_block_shape_independence():
    """Result must not depend on the BlockSpec tiling."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    q = _rand(k1, (1, 256, 2, 64), jnp.float32)
    k = _rand(k2, (1, 256, 2, 64), jnp.float32)
    v = _rand(k3, (1, 256, 2, 64), jnp.float32)
    a = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    b = flash_attention(q, k, v, block_q=64, block_k=256, interpret=True)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_flash_rejects_ragged():
    q = jnp.zeros((1, 100, 2, 64))
    k = jnp.zeros((1, 100, 2, 64))
    with pytest.raises(ValueError):
        flash_attention(q, k, k, block_q=64, interpret=True)


# ------------------------------------------------------------ decode attention

@pytest.mark.parametrize("b,smax,h,kv,d", [
    (1, 512, 4, 4, 64),
    (2, 1024, 8, 2, 64),
    (4, 512, 4, 1, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_sweep(b, smax, h, kv, d, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    q = _rand(k1, (b, 1, h, d), dtype)
    k = _rand(k2, (b, smax, kv, d), dtype)
    v = _rand(k3, (b, smax, kv, d), dtype)
    vl = smax // 2 + 17
    out = decode_attention(q, k, v, vl, interpret=True)
    exp = ref.decode_attention_ref(q, k, v, vl)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               **TOL[dtype])


@pytest.mark.parametrize("vl", [1, 511, 512])
def test_decode_valid_len_edges(vl):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(6), 3)
    q = _rand(k1, (1, 1, 4, 64), jnp.float32)
    k = _rand(k2, (1, 512, 2, 64), jnp.float32)
    v = _rand(k3, (1, 512, 2, 64), jnp.float32)
    out = decode_attention(q, k, v, vl, interpret=True)
    exp = ref.decode_attention_ref(q, k, v, vl)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)


def test_decode_sliding_window():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(k1, (2, 1, 4, 64), jnp.float32)
    k = _rand(k2, (2, 512, 2, 64), jnp.float32)
    v = _rand(k3, (2, 512, 2, 64), jnp.float32)
    out = decode_attention(q, k, v, 400, window=128, interpret=True)
    exp = ref.decode_attention_ref(q, k, v, 400, window=128)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------------- rmsnorm

@pytest.mark.parametrize("shape", [(4, 512), (2, 16, 256), (1, 128),
                                   (3, 5, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(8))
    x = _rand(k1, shape, dtype)
    g = _rand(k2, shape[-1:], dtype)
    out = rmsnorm(x, g, interpret=True)
    exp = ref.rmsnorm_ref(x, g)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               **TOL[dtype])


def test_rmsnorm_ragged_rows():
    """Row counts not divisible by the block run a ragged last block."""
    x = jax.random.normal(jax.random.PRNGKey(9), (19, 320))
    g = jnp.ones((320,))
    out = rmsnorm(x, g, block_rows=8, interpret=True)
    np.testing.assert_allclose(out, ref.rmsnorm_ref(x, g), atol=2e-5,
                               rtol=2e-5)


# ----------------------------------------------------------------- ops dispatch

def test_ops_dispatch_cpu_uses_ref(monkeypatch):
    from repro.kernels import ops
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 2, 32))
    out = ops.attention(q, q, q, None, jnp.float32, kind="causal")
    assert out.shape == q.shape


def test_ops_force_interpret(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "1")
    from repro.kernels import ops
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 2, 64))
    got = ops.attention(q, q, q, None, jnp.float32, kind="causal")
    exp = ref.flash_attention_ref(q, q, q, causal=True)
    np.testing.assert_allclose(got, exp, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kind,sk", [("causal", 200), ("decode", 600),
                                     (None, 128)])
def test_ops_attention_raises_on_tpu_without_a_kernel(monkeypatch, kind,
                                                      sk):
    """On a TPU, a call no kernel serves raises instead of quietly taking
    the reference (here: untiled flash and decode lengths, no kind)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    sq = 1 if kind == "decode" else sk
    q = jnp.zeros((1, sq, 4, 64))
    k = jnp.zeros((1, sk, 2, 64))
    with pytest.raises(NotImplementedError, match="no Pallas attention"):
        ops.attention(q, k, k, None, jnp.float32, kind=kind, valid_len=3)


# ------------------------------------------------------- xla_flash (+ VJP)

from repro.kernels.xla_flash import flash_attention_xla  # noqa: E402


@pytest.mark.parametrize("sq,sk,h,kv,causal,window", [
    (256, 256, 4, 4, True, 0),
    (128, 384, 4, 2, True, 0),
    (256, 256, 4, 1, False, 0),
    (256, 256, 8, 2, True, 64),
    (100, 200, 4, 2, True, 0),      # ragged -> padded path
])
def test_xla_flash_forward_and_grads(sq, sk, h, kv, causal, window):
    """Forward vs oracle AND custom-VJP gradients vs oracle autodiff."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (2, sq, h, 64))
    k = jax.random.normal(ks[1], (2, sk, kv, 64))
    v = jax.random.normal(ks[2], (2, sk, kv, 64))
    do = jax.random.normal(ks[3], (2, sq, h, 64))

    out = flash_attention_xla(q, k, v, causal=causal, window=window)
    exp = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, exp, atol=3e-5, rtol=3e-5)

    def f_flash(q, k, v):
        return (flash_attention_xla(q, k, v, causal=causal,
                                    window=window) * do).sum()

    def f_ref(q, k, v):
        return (ref.flash_attention_ref(q, k, v, causal=causal,
                                        window=window) * do).sum()

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_xla_flash_matches_pallas_interpret():
    """Both flash implementations agree with each other."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    a = flash_attention_xla(q, k, v, causal=True)
    b = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


# --------------------------------------------------------------- mamba scan

from repro.kernels.mamba_scan import mamba_scan  # noqa: E402


@pytest.mark.slow   # interpret-mode fori_loop over full sequences: ~3 min
@pytest.mark.parametrize("b,s,d,n,chunk,dblk", [
    (2, 512, 256, 16, 128, 128),
    (1, 256, 128, 32, 256, 128),    # single chunk
    (3, 384, 192, 16, 128, 192),    # non-pow2 batch/dims
    (2, 128, 256, 8, 64, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba_scan_sweep(b, s, d, n, chunk, dblk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    dt = jax.nn.softplus(_rand(ks[0], (b, s, d), dtype) * 0.3)
    x = _rand(ks[1], (b, s, d), dtype)
    bm = _rand(ks[2], (b, s, n), dtype) * 0.5
    cm = _rand(ks[3], (b, s, n), dtype) * 0.5
    a = -jnp.exp(jax.random.normal(ks[4], (d, n)) * 0.3)
    h0 = jax.random.normal(ks[5], (b, d, n), jnp.float32) * 0.1
    y, h = mamba_scan(dt, x, bm, cm, a, h0, chunk=chunk, d_block=dblk,
                      interpret=True)
    ye, he = ref.mamba_scan_ref(dt, x, bm, cm, a, h0)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ye, np.float32), **TOL[dtype])
    np.testing.assert_allclose(h, he, atol=5e-5, rtol=5e-5)


def test_mamba_state_carry_across_calls():
    """Two half-sequence kernel calls chained == one full-sequence call."""
    ks = jax.random.split(jax.random.PRNGKey(12), 6)
    b, s, d, n = 1, 256, 128, 16
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, s, d)) * 0.3)
    x = jax.random.normal(ks[1], (b, s, d))
    bm = jax.random.normal(ks[2], (b, s, n)) * 0.5
    cm = jax.random.normal(ks[3], (b, s, n)) * 0.5
    a = -jnp.exp(jax.random.normal(ks[4], (d, n)) * 0.3)
    h0 = jnp.zeros((b, d, n), jnp.float32)
    y_full, h_full = mamba_scan(dt, x, bm, cm, a, h0, chunk=128,
                                interpret=True)
    half = s // 2
    y1, h1 = mamba_scan(dt[:, :half], x[:, :half], bm[:, :half],
                        cm[:, :half], a, h0, chunk=128, interpret=True)
    y2, h2 = mamba_scan(dt[:, half:], x[:, half:], bm[:, half:],
                        cm[:, half:], a, h1, chunk=128, interpret=True)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], axis=1), y_full,
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(h2, h_full, atol=2e-5, rtol=2e-5)


def test_ops_mamba_chunk_interpret_matches_xla(monkeypatch):
    """ops dispatch: forced-interpret kernel path == associative-scan path."""
    from repro.kernels import ops
    ks = jax.random.split(jax.random.PRNGKey(13), 6)
    b, s, d, n = 2, 128, 64, 16
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, s, d)) * 0.3)
    x = jax.random.normal(ks[1], (b, s, d))
    bm = jax.random.normal(ks[2], (b, s, n)) * 0.5
    cm = jax.random.normal(ks[3], (b, s, n)) * 0.5
    a = -jnp.exp(jax.random.normal(ks[4], (d, n)) * 0.3)
    h0 = jnp.zeros((b, d, n), jnp.float32)
    y_xla, h_xla = ops.mamba_chunk(dt, x, bm, cm, a, h0)
    monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "1")
    y_k, h_k = ops.mamba_chunk(dt, x, bm, cm, a, h0)
    np.testing.assert_allclose(y_k, y_xla, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(h_k, h_xla, atol=2e-5, rtol=2e-5)
