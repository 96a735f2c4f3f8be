"""Weights made from the seed, on the device, in one jitted call.

The tree has the layout the system under test declares (``rt.specs``: one
leaf per parameter, layers stacked along a leading axis).  The values follow
the benchmark's own law, keyed by the leaf's path, so they do not depend on
the order of the tree or on how the leaves are sharded:

* norm scales: 1 + 0.1 * N(0, 1), so that a norm applied with the wrong
  scale shows in the logits;
* the embedding tables: N(0, 1) / sqrt(d_model), so that logits come out
  near unit scale;
* every other leaf: the law of the model type's ``leaf_law``
  (``bench/models/<model_type>.py``), for a matrix N(0, 1) / sqrt(fan_in),
  where fan_in is the width that the matrix contracts.

The reference reads these same arrays; nothing of them is made by the
program.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

NORM_JITTER = 0.1


def seed_words(seed: int) -> tuple[np.uint32, np.uint32]:
    """Any whole number up to 2**64 as two 32-bit words."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32)


def root_key(lo, hi):
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def _path_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def leaf_law(name: str, shape: tuple, d_model: int, model
             ) -> tuple[str, float]:
    """(kind, std) for the leaf at ``name``: kind "norm" or "normal"."""
    last = name.rsplit("/", 1)[-1]
    if "norm" in last:
        return "norm", NORM_JITTER
    if last in ("embed", "unembed"):
        return "normal", d_model ** -0.5
    return model.leaf_law(name, shape)


def make_params(shapes, d_model: int, seed: int, model,
                dtype=jnp.bfloat16, shardings=None):
    """Every leaf of ``shapes`` (a tree of objects with ``.shape``) drawn
    from ``seed`` in one jitted program, each leaf born in its sharding;
    ``model`` is the model type's module."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: hasattr(x, "shape"))
    plan = []
    for path, leaf in leaves:
        name = _path_name(path)
        shape = tuple(leaf.shape)
        kind, std = leaf_law(name, shape, d_model, model)
        plan.append((zlib.crc32(name.encode()), shape, kind, std))

    def build(lo, hi):
        key = root_key(lo, hi)
        out = []
        for tag, shape, kind, std in plan:
            z = jax.random.normal(jax.random.fold_in(key, tag), shape,
                                  jnp.float32)
            x = 1.0 + std * z if kind == "norm" else std * z
            out.append(x.astype(dtype))
        return out

    out_sh = (jax.tree.leaves(shardings) if shardings is not None else None)
    fn = jax.jit(build, out_shardings=out_sh)
    lo, hi = seed_words(seed)
    return jax.tree_util.tree_unflatten(treedef, fn(lo, hi))

