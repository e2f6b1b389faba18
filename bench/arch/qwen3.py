"""Qwen3 (``model_type`` "qwen3"): the served model's configuration, its
random weights, the float32 reference forward pass and the work a stage
requires, from the published ``config.json`` keys of a model entry.

The harness finds this module by the entry's ``model_type``
(``harness.load_arch``); another architecture is another file beside it
with the same five functions.

The decoder (arXiv:2505.09388; Hugging Face ``Qwen3ForCausalLM``):
RMSNorm pre-norms, GQA attention with per-head RMSNorm on q and k before
rotary embeddings (half-split rotation, ``rope_theta``) on every layer,
causal softmax, SwiGLU MLP, final RMSNorm, head tied to the embedding.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference as REF
import weights as W
import work as WK
from repro.config import ATTN_FULL, DENSE, ModelConfig


# ------------------------------------------------------------ the builder
def model_config(m: Mapping[str, Any], dtype: str) -> ModelConfig:
    if m["hidden_act"] != "silu" or not m["tie_word_embeddings"]:
        raise ValueError(f"{m['name']}: the Qwen3 stack takes SwiGLU and "
                         "tied embeddings")
    return ModelConfig(
        name=m["name"], family=DENSE, num_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], head_dim=m["head_dim"],
        block_pattern=(ATTN_FULL,), qk_norm=True,
        rope_theta=float(m["rope_theta"]), tie_embeddings=True,
        norm_eps=float(m["rms_norm_eps"]), act="silu",
        max_seq_len=int(m["max_position_embeddings"]), dtype=dtype)


def paged_attention_layers(m: Mapping[str, Any]) -> int:
    """Paged flash kernels in one pass: every layer is full attention."""
    return m["num_hidden_layers"]


# ------------------------------------------------------------ the weights
# the serving model's tree, layers stacked on a leading axis
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


def _make(m: Mapping[str, int], seed_lo, seed_hi, role: int):
    key = W.root_key(seed_lo, seed_hi, role)
    d, V = m["hidden_size"], m["vocab_size"]
    keys = jax.random.split(key, len(_LAYER_LEAVES) + 1)
    layer: Dict[str, Any] = {}
    for k, path in zip(keys[1:], _LAYER_LEAVES):
        shape = _layer_shape(m, path)
        if path[-1] == "scale":
            W.set_leaf(layer, path, jnp.ones(shape, jnp.float32))
        else:
            W.set_leaf(layer, path, W.matrix(k, shape))
    return {"embed": {"table": W.matrix(keys[0], (V, d))},
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "stages": (layer,), "tail": ()}


def make_params(m: Mapping[str, int], seed: int, role: int):
    return W.on_device(_make, m, seed, role)


# ---------------------------------------------------------- the reference
def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None]          # [S, dh/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _forward(params, tokens, n_valid, class_ids, *, theta, eps, control):
    """Class logits at position ``n_valid - 1`` of ``tokens`` [S] (the
    positions past ``n_valid`` are padding and are never attended); one
    sequence, layer by layer under ``lax.scan``, no cache, no kernels."""
    def f32(a, in_axes=None):
        return REF.as_f32(a, in_axes, control)
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = f32(params["embed"]["table"][tokens], (1,))
    causal = pos[None, :] <= pos[:, None]

    def layer(x, p):
        a = p["attn"]
        h = _rms(x, p["norm1"]["scale"], eps)
        q = jnp.einsum("sd,dhk->shk", h, f32(a["wq"], (0,)))
        k = jnp.einsum("sd,dhk->shk", h, f32(a["wk"], (0,)))
        v = jnp.einsum("sd,dhk->shk", h, f32(a["wv"], (0,)))
        q = _rope(_rms(q, a["q_norm"]["scale"], eps), pos, theta)
        k = _rope(_rms(k, a["k_norm"]["scale"], eps), pos, theta)
        g = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        s = jnp.einsum("qhk,shk->hqs", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, -1), v)
        x = x + jnp.einsum("qhk,hkd->qd", o, f32(a["wo"], (0, 1)))
        h = _rms(x, p["norm2"]["scale"], eps)
        m = p["mlp"]
        up = jax.nn.silu(h @ f32(m["w1"], (0,))) * (h @ f32(m["w3"], (0,)))
        return x + up @ f32(m["w2"], (0,)), None

    x, _ = jax.lax.scan(layer, x, params["stages"][0])
    last = _rms(jax.lax.dynamic_index_in_dim(x, n_valid - 1, 0, False),
                params["final_norm"]["scale"], eps)
    return f32(params["embed"]["table"][class_ids], (1,)) @ last


@functools.lru_cache(maxsize=None)
def _compiled(theta: float, eps: float, control: bool):
    return jax.jit(functools.partial(_forward, theta=theta, eps=eps,
                                     control=control))


def class_logits(params, m: Mapping[str, Any], tokens: Sequence[int],
                 n_classes: int, control: bool = False) -> np.ndarray:
    return REF.last_class_logits(
        _compiled(float(m["rope_theta"]), float(m["rms_norm_eps"]),
                  bool(control)), params, tokens, n_classes)


# ------------------------------------------------------------ the work
def matmul_params_per_layer(m: Mapping[str, int]) -> int:
    """Weights every token multiplies per layer: q, k, v, o and SwiGLU."""
    d, f = m["hidden_size"], m["intermediate_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def linear_flops_per_token(m: Mapping[str, int]) -> int:
    return 2 * m["num_hidden_layers"] * matmul_params_per_layer(m)


def attention_work(m: Mapping[str, int], start: int, stop: int
                   ) -> Tuple[int, int]:
    """(FLOPs, bytes) of attention for queries at positions [start, stop)
    over a causal prefix: QK^T and PV at 2 FLOPs a multiply-add; bytes are
    one read of every key and value up to ``stop`` and one read of Q and
    write of O per query, per layer, in bf16."""
    L, dh = m["num_hidden_layers"], m["head_dim"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    flops = L * 4 * h * dh * WK.keys_attended(start, stop)
    nbytes = L * WK.BF16_BYTES * (2 * kv * dh * stop
                                  + 2 * h * dh * (stop - start))
    return flops, nbytes


def stage_work(m: Mapping[str, int], cached: int, doc_tokens: int,
               op_tokens: int, n_classes: int) -> Dict[str, int]:
    """Work of one stage visit of one document (``work.py`` says what is
    counted): every layer's projections and SwiGLU for each new token,
    causal attention over true lengths, the tied head over the class
    rows."""
    assert 0 <= cached <= doc_tokens and op_tokens >= 0
    stop = doc_tokens + op_tokens
    tokens = stop - cached
    a_flops, a_bytes = attention_work(m, cached, stop)
    head = 2 * m["hidden_size"] * n_classes
    lin = linear_flops_per_token(m) * tokens
    return {"tokens": tokens, "linear_flops": lin, "attn_flops": a_flops,
            "attn_bytes": a_bytes, "head_flops": head,
            "flops": lin + a_flops + head}
