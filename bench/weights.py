"""Random Qwen3 weights, made on the device in one jitted call from the
seed, in the type they are served in (bf16 matrices, f32 norm scales).

The tree is laid out as the serving model takes its parameters
(``embed``/``final_norm``/``stages``/``tail``, layers stacked on a leading
axis); ``check_layout`` holds it against the model's own
``jax.eval_shape(model.init, ...)``.  Matrices are N(0, 0.02^2), the
published initializer range of the Qwen3 configs; norm scales are 1.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp

INIT_STD = 0.02

# leaf -> (shape from the model dims, dtype kind)
_LAYER_LEAVES = (
    ("norm1", "scale"), ("attn", "wq"), ("attn", "wk"), ("attn", "wv"),
    ("attn", "wo"), ("attn", "q_norm", "scale"), ("attn", "k_norm", "scale"),
    ("norm2", "scale"), ("mlp", "w1"), ("mlp", "w3"), ("mlp", "w2"),
)


def _layer_shape(m: Mapping[str, int], path) -> tuple:
    L, d, f = (m["num_hidden_layers"], m["hidden_size"],
               m["intermediate_size"])
    h, kv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    return {
        ("norm1", "scale"): (L, d), ("norm2", "scale"): (L, d),
        ("attn", "q_norm", "scale"): (L, dh),
        ("attn", "k_norm", "scale"): (L, dh),
        ("attn", "wq"): (L, d, h, dh), ("attn", "wk"): (L, d, kv, dh),
        ("attn", "wv"): (L, d, kv, dh), ("attn", "wo"): (L, h, dh, d),
        ("mlp", "w1"): (L, d, f), ("mlp", "w3"): (L, d, f),
        ("mlp", "w2"): (L, f, d),
    }[path]


def _set(tree: Dict[str, Any], path, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _make(m: Mapping[str, int], seed_lo, seed_hi, role: int):
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed_lo), seed_hi), role)
    d, V = m["hidden_size"], m["vocab_size"]
    keys = jax.random.split(key, len(_LAYER_LEAVES) + 1)
    layer: Dict[str, Any] = {}
    for k, path in zip(keys[1:], _LAYER_LEAVES):
        shape = _layer_shape(m, path)
        if path[-1] == "scale":
            _set(layer, path, jnp.ones(shape, jnp.float32))
        else:
            _set(layer, path, (INIT_STD * jax.random.normal(
                k, shape, jnp.float32)).astype(jnp.bfloat16))
    table = (INIT_STD * jax.random.normal(keys[0], (V, d), jnp.float32)
             ).astype(jnp.bfloat16)
    return {"embed": {"table": table},
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "stages": (layer,), "tail": ()}


def make_params(m: Mapping[str, int], seed: int, role: int):
    """One jitted call: the whole tree, on the default device."""
    fn = jax.jit(functools.partial(_make, dict(m), role=role))
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
