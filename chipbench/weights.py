"""Seeded random weights for a dense decoder, made on the device.

The benchmark makes the weights itself, so that the reference it compares
with takes nothing the program under test has made. They are laid out as
the program's parameter tree (``layout``: shapes and dtypes only, from
``jax.eval_shape`` of the program's init) and drawn in one jitted call,
directly in each leaf's served dtype.

Draws, by leaf name: matrices N(0, 1/fan_in); the embedding N(0,
1/hidden_size), so that tied logits have a spread of about 1; q/k/v biases
N(0, 0.25); norm weights 1 + N(0, 0.01).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MATRICES = ("wq", "wk", "wv", "wo", "wi", "wg")
BIASES = ("bq", "bk", "bv")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size: the low and high 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _leaf(key, path: tuple[str, ...], shape, dtype):
    name = path[-1]
    if name == "embed":
        return jax.random.normal(key, shape, dtype) * jnp.asarray(
            shape[-1] ** -0.5, dtype)
    if name in MATRICES:
        return jax.random.normal(key, shape, dtype) * jnp.asarray(
            shape[-2] ** -0.5, dtype)
    if name in BIASES:
        return jax.random.normal(key, shape, dtype) * jnp.asarray(0.5, dtype)
    if name == "w" and path[-2].endswith("norm"):
        return 1 + 0.1 * jax.random.normal(key, shape, dtype)
    raise ValueError(f"no draw for parameter {'/'.join(path)}")


def make_params(layout, seed: int):
    """Parameters shaped like ``layout``, drawn from ``seed`` on the
    default device in one compiled call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(layout)
    paths = [tuple(k.key for k in p) for p, _ in leaves]

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(leaves))
        return [_leaf(k, path, s.shape, s.dtype)
                for k, path, (_, s) in zip(keys, paths, leaves)]

    return jax.tree_util.tree_unflatten(treedef, draw(seed_key(seed)))
