"""Model weights made from the seed, on the device, in one jitted call.

Every value is a function of ``(seed, leaf name, layer, shape)`` alone,
so the served program's stacked parameters and the reference's weights,
made one layer at a time, are the same numbers without either taking
anything from the other.

Leaf names are ``/``-joined paths in the program's parameter tree
(``segments/0/0/core/wq``); leaves under ``segments`` carry a leading
layer axis. How each leaf is drawn is its family's to say: the family
file's ``init_rule(name, shape)`` (``bench/families/<family>.py``)
takes a leaf's name and one layer's shape and returns one of

* ``("embed",)``: N(0, 0.02^2);
* ``("scale",)``: 1 + N(0, 0.1^2), a norm's gain;
* ``("fan_in", axes)``: N(0, 1 / n), n the product of ``shape[a]``
  over ``axes``, so activations keep unit size through the depth,

and raises for a leaf it does not know. This file draws; it reads no
leaf's name.
"""

from __future__ import annotations

import math
import zlib
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp

Rule = Callable[[str, Tuple[int, ...]], Tuple]


def base_key(seed: int) -> jax.Array:
    """A key from any whole seed up to 63 bits (jax keys hold 32)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _draw(z: jax.Array, how: Sequence, shape) -> jax.Array:
    """Standard normal `z` scaled as the rule `how` says."""
    if how[0] == "embed":
        return 0.02 * z
    if how[0] == "scale":
        return 1.0 + 0.1 * z
    if how[0] == "fan_in":
        return z / math.sqrt(math.prod(shape[a] for a in how[1]))
    raise ValueError(f"unknown rule {how!r}")


def leaf(key: jax.Array, name: str, layer, shape, rule: Rule,
         dtype=jnp.float32) -> jax.Array:
    """One layer's value of leaf `name` (`layer` may be traced), drawn
    as ``rule(name, shape)`` says."""
    k = jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(name.encode())),
                           layer)
    z = jax.random.normal(k, shape, jnp.float32)
    return _draw(z, rule(name, tuple(shape)), shape).astype(dtype)


def _path_name(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def make_params(key: jax.Array, shapes, rule: Rule):
    """The whole parameter tree for `shapes` (a pytree of
    ``ShapeDtypeStruct``, as ``jax.eval_shape(model.init, ...)`` gives),
    each leaf drawn by `rule`, in one jitted call on the default
    device."""

    def build(key):
        def one(path, s):
            name = _path_name(path)
            if name.startswith("segments/"):
                return jax.vmap(lambda l: leaf(key, name, l, s.shape[1:],
                                               rule, s.dtype))(
                    jnp.arange(s.shape[0]))
            return leaf(key, name, 0, s.shape, rule, s.dtype)
        return jax.tree_util.tree_map_with_path(one, shapes)

    return jax.jit(build)(key)
