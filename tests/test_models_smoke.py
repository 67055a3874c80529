"""Per-architecture smoke tests (deliverable f).

Each assigned architecture instantiates a REDUCED same-family variant
(<=2 pattern units, d_model<=256, <=4 experts) and runs forward + one
train step on CPU, asserting output shapes and no NaNs. Decode-path
consistency (prefill + step == full forward) covers the cache logic.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_arch, get_smoke
from repro.models import build_model
from repro.models.config import scale_down
from repro.train.optimizer import AdamW
from repro.train.trainer import make_train_step

ALL = ARCH_IDS + ["llama3.2-1b-sw"]


def _batch(cfg, key, b=2, t=16):
    ks = jax.random.split(key, 3)
    batch = {"tokens": jax.random.randint(ks[0], (b, t), 0, cfg.vocab_size)}
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(ks[1], (b, 8, 128))
    if cfg.num_image_tokens:
        batch["image_feats"] = jax.random.normal(
            ks[2], (b, cfg.num_image_tokens, 1024))
    return batch


@pytest.fixture(scope="module")
def built():
    out = {}
    for aid in ALL:
        cfg = get_smoke(aid)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        out[aid] = (cfg, model, params)
    return out


@pytest.mark.parametrize("aid", ALL)
def test_forward_shapes_and_finite(built, aid):
    cfg, model, params = built[aid]
    batch = _batch(cfg, jax.random.PRNGKey(1))
    logits, aux = model.forward(params, batch)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("aid", ALL)
def test_one_train_step(built, aid):
    cfg, model, params = built[aid]
    opt = AdamW(lr=1e-3)
    step = jax.jit(make_train_step(model, opt))
    opt_state = opt.init(params)
    batch = _batch(cfg, jax.random.PRNGKey(2))
    new_params, new_state, metrics = step(params, opt_state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    # params actually moved
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), params, new_params)
    assert max(jax.tree_util.tree_leaves(moved)) > 0


def _check_decode(cfg, model, params, b, prompt, total):
    """Prefill `prompt` tokens, then decode one token at a time up to
    `total`: every step's logits match the full forward's."""
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, total), 0,
                              cfg.vocab_size)
    batch = _batch(cfg, jax.random.PRNGKey(4), b=b, t=total)
    batch["tokens"] = toks
    full, _ = model.forward(params, batch)
    pre = dict(batch)
    pre["tokens"] = toks[:, :prompt]
    npfx = cfg.num_image_tokens or 0
    lg, cache = model.prefill(params, pre, smax=total + npfx)
    np.testing.assert_allclose(lg[:, -1], full[:, prompt - 1], atol=5e-4,
                               rtol=1e-3)
    for i in range(prompt, total):
        lg, cache = model.decode_step(params, toks[:, i : i + 1],
                                      jnp.int32(i + npfx), cache)
        np.testing.assert_allclose(lg[:, 0], full[:, i], atol=5e-4,
                                   rtol=1e-3)


@pytest.mark.parametrize("aid", ALL)
def test_decode_matches_forward(built, aid):
    """Prefill + stepwise decode reproduces full-forward logits."""
    cfg, model, params = built[aid]
    _check_decode(cfg, model, params, b=1, prompt=9, total=12)


@pytest.mark.parametrize("aid,window,prompt,total", [
    ("phi3-mini-3.8b", None, 9, 12),            # MHA
    ("llama3.2-1b", None, 9, 12),               # GQA
    ("granite-34b", None, 9, 12),               # MQA
    ("llama3.2-1b-sw", 8, 5, 14),               # ring buffer wraps in decode
    ("llama3.2-1b-sw", 8, 11, 14),              # prompt longer than window
    ("jamba-1.5-large-398b", None, 9, 12),      # attention + mamba segment
])
def test_decode_matches_forward_batched(built, aid, window, prompt, total):
    """At batch 3 each row's cache rows stay its own: the head-major
    stacks are written and read per row, per layer, per slot."""
    cfg, model, params = built[aid]
    if window is not None:
        cfg = dataclasses.replace(cfg, sliding_window=window)
        model = build_model(cfg)
    _check_decode(cfg, model, params, b=3, prompt=prompt, total=total)


@pytest.mark.parametrize("aid", ALL)
def test_loss_decreases_over_steps(built, aid):
    """5 steps on one repeated batch must reduce the loss (overfit check)."""
    cfg, model, params = built[aid]
    opt = AdamW(lr=3e-3)
    step = jax.jit(make_train_step(model, opt))
    state = opt.init(params)
    batch = _batch(cfg, jax.random.PRNGKey(5))
    losses = []
    for _ in range(5):
        params, state, metrics = step(params, state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


def test_full_configs_match_assignment():
    """The FULL configs carry the exact assigned hyperparameters."""
    expect = {
        "whisper-small": dict(d_model=768, num_heads=12, num_kv_heads=12,
                              d_ff=3072, vocab_size=51865, layers=12),
        "granite-34b": dict(d_model=6144, num_heads=48, num_kv_heads=1,
                            d_ff=24576, vocab_size=49152, layers=88),
        "deepseek-v3-671b": dict(d_model=7168, num_heads=128,
                                 num_kv_heads=128, vocab_size=129280,
                                 layers=61),
        "phi3-mini-3.8b": dict(d_model=3072, num_heads=32, num_kv_heads=32,
                               d_ff=8192, vocab_size=32064, layers=32),
        "pixtral-12b": dict(d_model=5120, num_heads=32, num_kv_heads=8,
                            d_ff=14336, vocab_size=131072, layers=40),
        "qwen2-72b": dict(d_model=8192, num_heads=64, num_kv_heads=8,
                          d_ff=29568, vocab_size=152064, layers=80),
        "xlstm-125m": dict(d_model=768, num_heads=4, vocab_size=50304,
                           layers=12),
        "jamba-1.5-large-398b": dict(d_model=8192, num_heads=64,
                                     num_kv_heads=8, d_ff=24576,
                                     vocab_size=65536, layers=72),
        "granite-moe-1b-a400m": dict(d_model=1024, num_heads=16,
                                     num_kv_heads=8, vocab_size=49155,
                                     layers=24),
        "llama3.2-1b": dict(d_model=2048, num_heads=32, num_kv_heads=8,
                            d_ff=8192, vocab_size=128256, layers=16),
    }
    for aid, ex in expect.items():
        cfg = get_arch(aid)
        assert cfg.d_model == ex["d_model"], aid
        assert cfg.num_heads == ex["num_heads"], aid
        assert cfg.vocab_size == ex["vocab_size"], aid
        assert cfg.num_layers == ex["layers"], aid
        if "num_kv_heads" in ex:
            assert cfg.num_kv_heads == ex["num_kv_heads"], aid
        if "d_ff" in ex:
            assert cfg.d_ff == ex["d_ff"], aid


def test_moe_configs():
    ds = get_arch("deepseek-v3-671b")
    assert ds.num_experts == 256 and ds.num_experts_per_tok == 8
    assert ds.num_shared_experts == 1 and ds.use_mla and ds.mtp_depth == 1
    ja = get_arch("jamba-1.5-large-398b")
    assert ja.num_experts == 16 and ja.num_experts_per_tok == 2
    gm = get_arch("granite-moe-1b-a400m")
    assert gm.num_experts == 32 and gm.num_experts_per_tok == 8


def test_param_counts_in_expected_range():
    """Total parameter counts land near the advertised sizes."""
    expect_b = {
        "granite-34b": (30, 40),
        "deepseek-v3-671b": (600, 740),
        "phi3-mini-3.8b": (3.3, 4.4),
        "pixtral-12b": (10, 14),
        "qwen2-72b": (63, 80),
        "jamba-1.5-large-398b": (340, 440),
        "llama3.2-1b": (0.9, 1.6),
        "xlstm-125m": (0.09, 0.2),
    }
    for aid, (lo, hi) in expect_b.items():
        n = get_arch(aid).param_count() / 1e9
        assert lo <= n <= hi, f"{aid}: {n:.2f}B outside [{lo},{hi}]"


def test_long_context_support_flags():
    assert not get_arch("llama3.2-1b").supports_long_context()
    assert get_arch("llama3.2-1b-sw").supports_long_context()
    assert get_arch("xlstm-125m").supports_long_context()
    assert get_arch("jamba-1.5-large-398b").supports_long_context()
    assert not get_arch("qwen2-72b").supports_long_context()


def test_scale_down_bounds():
    for aid in ARCH_IDS:
        cfg = scale_down(get_arch(aid))
        assert cfg.d_model <= 512
        assert cfg.num_experts <= 4
        assert cfg.num_layers <= 8
