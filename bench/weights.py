"""Random weights, made on the device in one jitted call from the seed, in
the type they are served in (bf16 matrices, f32 norm scales).

An architecture's module (``arch/<model_type>.py``) lays the tree out as
the serving model takes its parameters and builds it from these pieces;
``check_layout`` holds every tree against the model's own
``jax.eval_shape(model.init, ...)``.  Matrices are N(0, 0.02^2), the
published initializer range of the configs; norm scales are 1.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Mapping

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def root_key(seed_lo, seed_hi, role: int):
    """The key one model's leaves are split from: both 32-bit halves of
    the seed, then the model's role, folded in."""
    return jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed_lo), seed_hi), role)


def matrix(key, shape) -> jnp.ndarray:
    return (INIT_STD * jax.random.normal(key, shape, jnp.float32)
            ).astype(jnp.bfloat16)


def set_leaf(tree: Dict[str, Any], path, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def on_device(make: Callable, m: Mapping[str, Any], seed: int, role: int):
    """``make(m, seed_lo, seed_hi, role=role)`` as one jitted call: the
    whole tree, on the default device."""
    fn = jax.jit(functools.partial(make, dict(m), role=role))
    seed = int(seed)
    return fn(jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32) & 0xFFFFFFFF))


def check_layout(params, expected) -> None:
    """Raise unless ``params`` matches the model's own parameter tree
    (structure, shapes and dtypes) — ``expected`` is
    ``jax.eval_shape(model.init, key)``."""
    got = jax.tree_util.tree_structure(params)
    want = jax.tree_util.tree_structure(expected)
    if got != want:
        raise ValueError(f"weight tree differs from the model's:\n{got}\n"
                         f"vs\n{want}")
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(expected)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"leaf {a.shape}/{a.dtype} where the model "
                             f"has {b.shape}/{b.dtype}")
