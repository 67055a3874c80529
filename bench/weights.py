"""Model weights made from the seed, on the device, in one jitted call.

Every value is a function of ``(seed, leaf name, layer, shape)`` alone,
so the served program's stacked parameters and the reference's weights,
made one layer at a time, are the same numbers without either taking
anything from the other.

Leaf names are ``/``-joined paths in the program's parameter tree
(``segments/0/0/core/wq``); leaves under ``segments`` carry a leading
layer axis. Scales follow the usual fan-in rule, so activations keep
unit size through the depth:

* ``embed``, ``unembed``: N(0, 0.02^2)
* norm ``scale``: 1 + N(0, 0.1^2)
* ``wo`` (heads, head_dim, d): N(0, 1 / (heads * head_dim))
* any other matrix: N(0, 1 / fan_in), fan_in its first axis.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def base_key(seed: int) -> jax.Array:
    """A key from any whole seed up to 63 bits (jax keys hold 32)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def leaf(key: jax.Array, name: str, layer, shape, dtype=jnp.float32
         ) -> jax.Array:
    """One layer's value of leaf `name` (`layer` may be traced)."""
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


def _path_name(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def make_params(key: jax.Array, shapes):
    """The whole parameter tree for `shapes` (a pytree of
    ``ShapeDtypeStruct``, as ``jax.eval_shape(model.init, ...)`` gives),
    in one jitted call on the default device."""

    def build(key):
        def one(path, s):
            name = _path_name(path)
            if name.startswith("segments/"):
                return jax.vmap(lambda l: leaf(key, name, l, s.shape[1:],
                                               s.dtype))(
                    jnp.arange(s.shape[0]))
            return leaf(key, name, 0, s.shape, s.dtype)
        return jax.tree_util.tree_map_with_path(one, shapes)

    return jax.jit(build)(key)
