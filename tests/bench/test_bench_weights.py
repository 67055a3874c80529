"""Seeded weights: each family says how each leaf is drawn, and the
dense family's rule draws what the name-based rule it replaced drew."""

import math
import sys
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench.families import dense  # noqa: E402
from bench.weights import base_key, leaf, make_params  # noqa: E402
from repro.configs import get_smoke  # noqa: E402
from repro.models import build_model  # noqa: E402
from smallcell import small_cell  # noqa: E402

SEED = 2**31 + 23


def _old_leaf(key, name, layer, shape, dtype=jnp.float32):
    """The rule every leaf was drawn by before families gave their own:
    chosen by the last part of the leaf's name."""
    k = jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(name.encode())),
                           layer)
    z = jax.random.normal(k, shape, jnp.float32)
    last = name.rsplit("/", 1)[-1]
    if last in ("embed", "unembed"):
        v = 0.02 * z
    elif last == "scale":
        v = 1.0 + 0.1 * z
    elif last == "wo":
        v = z / math.sqrt(shape[0] * shape[1])
    else:
        v = z / math.sqrt(shape[0])
    return v.astype(dtype)


def _old_make_params(key, shapes):
    def build(key):
        def one(path, s):
            name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                            for p in path)
            if name.startswith("segments/"):
                return jax.vmap(lambda l: _old_leaf(key, name, l,
                                                    s.shape[1:], s.dtype))(
                    jnp.arange(s.shape[0]))
            return _old_leaf(key, name, 0, s.shape, s.dtype)
        return jax.tree_util.tree_map_with_path(one, shapes)
    return jax.jit(build)(key)


def _names(tree):
    return ["/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@pytest.fixture(scope="module")
def smoke():
    cell = small_cell("phi3-mini-3.8b", "phi3-overload", 20.0)
    arch = dense.build_arch(cell.config, get_smoke)
    shapes = jax.eval_shape(build_model(arch).init, jax.random.PRNGKey(0))
    return cell.config, shapes


def test_dense_weights_are_bit_identical(smoke):
    config, shapes = smoke
    key = base_key(SEED)
    new = make_params(key, shapes, dense.init_rule)
    old = _old_make_params(key, shapes)
    names = _names(shapes)
    assert {"embed", "unembed", "final_norm/scale"} <= set(names)
    for name, a, b in zip(names, jax.tree_util.tree_leaves(new),
                          jax.tree_util.tree_leaves(old)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    # the reference's own draws, one layer at a time, each compiled as
    # the reference compiles it
    a = dense.arch(config)
    old_layer = jax.jit(lambda key, layer: {
        n: _old_leaf(key, dense.LAYER + n, layer, shape)
        for n, shape in dense._layer_shapes(a).items()})
    for layer in range(a.layers):
        got, want = dense._layer_weights(key, a, layer), old_layer(key, layer)
        for n in dense._layer_shapes(a):
            assert np.array_equal(np.asarray(got[n]), np.asarray(want[n])), n
    for name, shape in (("embed", (a.vocab, a.d)),
                        ("final_norm/scale", (a.d,)),
                        ("unembed", (a.d, a.vocab))):
        got = jax.jit(lambda k: leaf(k, name, 0, shape, dense.init_rule))
        want = jax.jit(lambda k: _old_leaf(k, name, 0, shape))
        assert np.array_equal(np.asarray(got(key)), np.asarray(want(key))), \
            name


def test_a_family_rule_sets_each_leaf_scale():
    """A rule that names an expert stack's fan-in axis and a latent
    norm's gain draws each at unit scale, whatever its first axis."""
    seen = {}

    def rule(name, shape):
        seen[name] = shape
        if name.endswith("ffn/wg"):
            return ("fan_in", (1,))
        if name.endswith("q_norm"):
            return ("scale",)
        raise ValueError(name)

    sds = jax.ShapeDtypeStruct
    shapes = {"segments": {"0": {"0": {
        "ffn": {"wg": sds((3, 8, 256, 64), jnp.float32)},
        "core": {"q_norm": sds((3, 1536), jnp.float32)}}}}}
    p = make_params(base_key(SEED), shapes, rule)
    assert seen == {"segments/0/0/ffn/wg": (8, 256, 64),
                    "segments/0/0/core/q_norm": (1536,)}
    layer = p["segments"]["0"]["0"]
    wg = np.asarray(layer["ffn"]["wg"])
    assert wg.shape == (3, 8, 256, 64)
    assert np.std(wg) * math.sqrt(256) == pytest.approx(1.0, rel=0.05)
    q = np.asarray(layer["core"]["q_norm"])
    assert np.mean(q) == pytest.approx(1.0, abs=0.05)
    assert np.std(q) == pytest.approx(0.1, rel=0.1)


def test_an_undeclared_leaf_is_an_error(smoke):
    _, shapes = smoke
    with pytest.raises(ValueError, match="router"):
        dense.init_rule("segments/0/0/ffn/router", (256, 8))
    with pytest.raises(ValueError, match="kv_norm"):
        dense.init_rule("segments/0/0/core/kv_norm/scale_x", (512,))
    bigger = dict(shapes, extra={"router": jax.ShapeDtypeStruct(
        (16, 4), jnp.float32)})
    with pytest.raises(ValueError, match="extra/router"):
        make_params(base_key(SEED), bigger, dense.init_rule)
    with pytest.raises(ValueError, match="unknown rule"):
        leaf(base_key(SEED), "w", 0, (4, 4), lambda n, s: ("uniform",))
