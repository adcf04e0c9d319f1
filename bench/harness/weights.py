"""Random weights made on the device from the seed, in the type they are
served or trained in, in one jitted call.

The benchmark makes the weights, not the program: the reference takes
the same arrays, so nothing it reads was made by the code under test.
Each leaf draws from ``fold_in(key, leaf_index)``, so one leaf can be
made again on its own (``make_leaf``) with the same values.

Scales by the leaf's role, read from its path in the parameter tree:
matrices N(0, 1/fan_in) (a block-sparse slab's fan-in is its
``d_in_b * bL`` surviving inputs), the embedding N(0, 1/d_model), biases
N(0, 0.5^2), norm scales N(0, 0.1^2) (the program's RMSNorm multiplies
by 1 + scale).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, salt: int = 0) -> jax.Array:
    """A JAX key holding all of a (possibly > 32-bit) seed."""
    words = np.random.SeedSequence([int(seed), int(salt)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


def _path_names(path) -> list:
    out = []
    for k in path:
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                out.append(str(getattr(k, attr)))
                break
    return out


_JUNCTIONS = ("up", "gate", "down")


def leaf_scale(names, shape, d_model: int) -> float:
    """Standard deviation of one parameter leaf, from its path."""
    last = names[-1]
    if last == "scale":
        return 0.1
    if last == "b":
        return 0.5
    if last == "table":
        return float(1.0 / np.sqrt(d_model))
    if len(shape) >= 4 and "ffn" in names and (
            last in _JUNCTIONS or names[-2] in _JUNCTIONS):
        # block-sparse slab (..., n_rb, d_in_b, bL, bR)
        return float(1.0 / np.sqrt(shape[-3] * shape[-2]))
    return float(1.0 / np.sqrt(shape[-2]))


def _leaf(key, i, names, s, d_model):
    k = jax.random.fold_in(key, i)
    std = leaf_scale(names, s.shape, d_model)
    return (jax.random.normal(k, s.shape, jnp.float32) * std).astype(s.dtype)


def make_params(model, seed: int, salt: int = 0):
    """The model's parameter tree, filled from ``seed`` on the default
    device in one jitted call."""
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    d_model = model.cfg.d_model
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        leaves = [_leaf(key, i, _path_names(p), s, d_model)
                  for i, (p, s) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed, salt))


def diff_norms(model, seed: int, params, salt: int = 0):
    """Per-leaf ||params - initial params|| with the initial leaves made
    again from the seed inside one jitted call (no second copy of the
    tree is kept)."""
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    d_model = model.cfg.d_model
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)

    def norms(key, leaves):
        out = []
        for i, ((p, s), x) in enumerate(zip(flat, leaves)):
            x0 = _leaf(key, i, _path_names(p), s, d_model)
            out.append(jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32) - x0.astype(jnp.float32)))))
        return jnp.stack(out)

    return np.asarray(jax.jit(norms)(seed_key(seed, salt),
                                     jax.tree.leaves(params)))


def leaf_names(model) -> list:
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return ["/".join(_path_names(p)) for p, _ in flat]
