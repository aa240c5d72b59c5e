"""Seeded random weights, made on the device: shared draws plus the
family's.

The benchmark makes the weights itself, so that the reference it compares
with takes nothing the program under test has made. They are laid out as
the program's parameter tree (``layout``: shapes and dtypes only, from
``jax.eval_shape`` of the program's init) and drawn in one jitted call,
directly in each leaf's served dtype.

Shared draws, by leaf name, for any family: matrices and the untied output
head N(0, 1/fan_in); the embedding N(0, 1/hidden_size), so that tied
logits have a spread of about 1; q/k/v biases N(0, 0.25); norm weights
1 + N(0, 0.01). A family's own leaves are drawn by the ``DRAWS`` of its
file ``chipbench/reference/<family>.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _matrix(key, shape, dtype):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(
        shape[-2] ** -0.5, dtype)


def _embed(key, shape, dtype):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(
        shape[-1] ** -0.5, dtype)


def _bias(key, shape, dtype):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(0.5, dtype)


def _norm(key, shape, dtype):
    return 1 + 0.1 * jax.random.normal(key, shape, dtype)


SHARED = {"embed": _embed, "head": _matrix,
          **dict.fromkeys(("wq", "wk", "wv", "wo", "wi", "wg"), _matrix),
          **dict.fromkeys(("bq", "bk", "bv"), _bias)}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size: the low and high 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _draw_for(path: tuple[str, ...], draws: dict):
    """The draw of a leaf: the shared table's, else the family's."""
    name = path[-1]
    if name == "w" and len(path) > 1 and path[-2].endswith("norm"):
        return _norm
    draw = SHARED.get(name, draws.get(name))
    if draw is None:
        raise ValueError(f"no draw for parameter {'/'.join(path)}")
    return draw


def make_params(layout, seed: int, draws: dict | None = None):
    """Parameters shaped like ``layout``, drawn from ``seed`` on the
    default device in one compiled call. ``draws`` maps the family's own
    leaf names to ``draw(key, shape, dtype)``; a name the shared table
    draws is an error, since that entry could never be used."""
    draws = draws or {}
    taken = sorted(set(draws) & set(SHARED))
    if taken:
        raise ValueError(f"family draws for {', '.join(taken)}: the shared "
                         "table draws these leaves")
    leaves, treedef = jax.tree_util.tree_flatten_with_path(layout)
    paths = [tuple(k.key for k in p) for p, _ in leaves]
    fns = [_draw_for(path, draws) for path in paths]

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(leaves))
        return [fn(k, s.shape, s.dtype)
                for fn, k, (_, s) in zip(fns, keys, leaves)]

    return jax.tree_util.tree_unflatten(treedef, draw(seed_key(seed)))
