"""Granite 4.0 hybrid (``model_type`` "granitemoehybrid"), dense variant:
the served model's configuration, its random weights, the float32
reference forward pass and the work a stage requires, from the published
``config.json`` keys of a model entry.

The decoder (Hugging Face ``GraniteMoeHybridForCausalLM`` with
``num_local_experts`` 0): embeddings x ``embedding_multiplier``; per
layer an RMSNorm, the mixer ``layer_types`` names, the residual added at
x ``residual_multiplier``, an RMSNorm, the shared SwiGLU MLP, the
residual again x ``residual_multiplier``; a final RMSNorm; the head
tied to the embedding, logits / ``logits_scaling``.

- ``mamba``: Mamba-2 with one group (Dao & Gu 2024, arXiv:2405.21060):
  in_proj (no bias) -> z | xBC | dt; a width-``mamba_d_conv`` depthwise
  causal conv with bias over xBC, SiLU; x [heads, head_dim], B, C
  [d_state]; dt = softplus(dt + dt_bias), A = -exp(A_log); the selective
  scan state = exp(dt A) state + dt x (outer) B, y = state . C + D x;
  y * SiLU(z) through an RMSNorm over d_inner (``rms_norm_eps``);
  out_proj.
- ``attention``: GQA with no positions (``position_embedding_type``
  "nope"), softmax scale ``attention_multiplier``, no biases.

The reference runs the scan one token at a time (``lax.scan``), attention
one KV head group at a time.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference as REF
import weights as W
import work as WK
from repro.config import ATTN_FULL, HYBRID, MAMBA2, ModelConfig

KINDS = {"mamba": MAMBA2, "attention": ATTN_FULL}


# ------------------------------------------------------ the configuration
def _period(types: Sequence[str]) -> Tuple[str, ...]:
    """The shortest repeating unit of ``layer_types``."""
    n = len(types)
    for p in range(1, n + 1):
        if n % p == 0 and all(types[i] == types[i % p] for i in range(n)):
            return tuple(types[:p])
    raise AssertionError


def _head_dim(m: Mapping[str, Any]) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


def model_config(m: Mapping[str, Any], dtype: str) -> ModelConfig:
    refusals = {
        "a dense model (num_local_experts 0)": m["num_local_experts"] == 0,
        "SwiGLU (hidden_act silu)": m["hidden_act"] == "silu",
        "tied embeddings": m["tie_word_embeddings"],
        "no positions (position_embedding_type nope)":
            m["position_embedding_type"] == "nope",
        "one Mamba group": m["mamba_n_groups"] == 1,
        "a conv bias and no projection bias":
            m["mamba_conv_bias"] and not m["mamba_proj_bias"],
        "no attention bias": not m["attention_bias"],
        "heads x head_dim = expand x hidden":
            m["mamba_n_heads"] * m["mamba_d_head"]
            == m["mamba_expand"] * m["hidden_size"],
        "mamba and attention layers only":
            set(m["layer_types"]) <= set(KINDS),
    }
    missing = [k for k, ok in refusals.items() if not ok]
    if missing:
        raise ValueError(f"{m['name']}: the granite hybrid stack takes "
                         + "; ".join(missing))
    return ModelConfig(
        name=m["name"], family=HYBRID, num_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        d_ff=m["shared_intermediate_size"], vocab_size=m["vocab_size"],
        head_dim=_head_dim(m),
        block_pattern=tuple(KINDS[t] for t in _period(m["layer_types"])),
        norm_eps=float(m["rms_norm_eps"]), act="silu", tie_embeddings=True,
        embedding_multiplier=float(m["embedding_multiplier"]),
        residual_multiplier=float(m["residual_multiplier"]),
        attention_multiplier=float(m["attention_multiplier"]),
        logits_scaling=float(m["logits_scaling"]), use_rope=False,
        ssm_heads=m["mamba_n_heads"], ssm_head_dim=m["mamba_d_head"],
        ssm_state=m["mamba_d_state"], ssm_conv=m["mamba_d_conv"],
        ssm_chunk=m["mamba_chunk_size"],
        max_seq_len=int(m["max_position_embeddings"]), dtype=dtype)


def pallas_kernels(m: Mapping[str, Any]) -> List[str]:
    """The Pallas kernels of one pass, in layer order: ``scan`` (the SSD
    chunk scan) for a Mamba layer, ``flash`` (paged flash attention) for
    an attention layer."""
    return ["scan" if t == "mamba" else "flash" for t in m["layer_types"]]


def paged_attention_layers(m: Mapping[str, Any]) -> int:
    """Pallas kernels in one pass (``op_suffix_share``'s position rule):
    one a layer, flash or scan."""
    return len(pallas_kernels(m))


# ------------------------------------------------------------ the weights
def _conv_dim(m):
    return m["mamba_n_heads"] * m["mamba_d_head"] + 2 * m["mamba_d_state"]


def _entry_shapes(m, kind: str, R: int) -> Dict[Tuple[str, ...], tuple]:
    """Leaf shapes of one pattern position, stacked over ``R``
    repetitions, as the serving model lays them out."""
    d, f = m["hidden_size"], m["shared_intermediate_size"]
    out = {("norm1", "scale"): (R, d), ("norm2", "scale"): (R, d),
           ("mlp", "w1"): (R, d, f), ("mlp", "w3"): (R, d, f),
           ("mlp", "w2"): (R, f, d)}
    if kind == "mamba":
        H, P = m["mamba_n_heads"], m["mamba_d_head"]
        di, cd = H * P, _conv_dim(m)
        out.update({
            ("mamba", "in_proj"): (R, d, di + cd + H),
            ("mamba", "conv_w"): (R, m["mamba_d_conv"], cd),
            ("mamba", "conv_b"): (R, cd),
            ("mamba", "dt_bias"): (R, H), ("mamba", "A_log"): (R, H),
            ("mamba", "D"): (R, H), ("mamba", "norm", "scale"): (R, di),
            ("mamba", "out_proj"): (R, di, d)})
    else:
        h, kv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                     _head_dim(m))
        out.update({("attn", "wq"): (R, d, h, dh),
                    ("attn", "wk"): (R, d, kv, dh),
                    ("attn", "wv"): (R, d, kv, dh),
                    ("attn", "wo"): (R, h, dh, d)})
    return out


def _leaf(key, path, shape, m):
    """Matrices N(0, 0.02^2) in bf16, norm scales 1; the Mamba-2
    initialisation for A (A_log = log U[1, 16]), dt (log-uniform in
    [0.001, 0.1], stored as softplus^-1) and D (1); conv weight and bias
    U(-1/sqrt(width), +), PyTorch's Conv1d default, in bf16."""
    name = path[-1]
    if name == "scale" or name == "D":
        return jnp.ones(shape, jnp.float32)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name in ("conv_w", "conv_b"):
        bound = 1.0 / math.sqrt(m["mamba_d_conv"])
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(jnp.bfloat16)
    return W.matrix(key, shape)


def _make(m: Mapping[str, Any], seed_lo, seed_hi, role: int):
    key = W.root_key(seed_lo, seed_hi, role)
    unit = _period(m["layer_types"])
    R = len(m["layer_types"]) // len(unit)
    k_emb, k_layers = jax.random.split(key)
    stages = []
    for i, kind in enumerate(unit):
        shapes = _entry_shapes(m, kind, R)
        keys = jax.random.split(jax.random.fold_in(k_layers, i), len(shapes))
        entry: Dict[str, Any] = {}
        for k, (path, shape) in zip(keys, sorted(shapes.items())):
            W.set_leaf(entry, path, _leaf(k, path, shape, m))
        stages.append(entry)
    d, V = m["hidden_size"], m["vocab_size"]
    return {"embed": {"table": W.matrix(k_emb, (V, d))},
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "stages": tuple(stages), "tail": ()}


def make_params(m: Mapping[str, Any], seed: int, role: int):
    return W.on_device(_make, m, seed, role)


# ---------------------------------------------------------- the reference
def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _mamba(p, h, m, f32, eps):
    """One Mamba-2 mixer over one sequence ``h`` [S, d], token by token."""
    H, P, N = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    K, di = m["mamba_d_conv"], H * P
    zxbcdt = h @ f32(p["in_proj"], (0,))
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * N],
                  zxbcdt[:, 2 * di + 2 * N:])
    S = h.shape[0]
    w = p["conv_w"].astype(jnp.float32)
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(p["conv_b"].astype(jnp.float32)
                      + sum(padded[k:k + S] * w[k] for k in range(K)))
    x = xbc[:, :di].reshape(S, H, P)
    bm, cm = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                      # [S, H]
    A = -jnp.exp(p["A_log"])

    def step(state, t):
        xt, dtt, bt, ct = t
        state = (jnp.exp(dtt * A)[:, None, None] * state
                 + (dtt[:, None] * xt)[..., None] * bt[None, None])
        return state, jnp.einsum("hpn,n->hp", state, ct)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, bm, cm))
    y = (y + p["D"][:, None] * x).reshape(S, di) * jax.nn.silu(z)
    return _rms(y, p["norm"]["scale"], eps) @ f32(p["out_proj"], (0,))


def _attention(a, h, m, f32, causal):
    """NoPE GQA, one KV head group at a time (``lax.map``)."""
    q = jnp.einsum("sd,dhk->hsk", h, f32(a["wq"], (0,)))
    k = jnp.einsum("sd,dhk->hsk", h, f32(a["wk"], (0,)))
    v = jnp.einsum("sd,dhk->hsk", h, f32(a["wv"], (0,)))
    g = q.shape[0] // k.shape[0]
    q = q.reshape((k.shape[0], g) + q.shape[1:])

    def group(qkv):
        qg, kg, vg = qkv
        s = jnp.einsum("gqk,sk->gqs", qg, kg) * m["attention_multiplier"]
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("gqs,sk->gqk", jax.nn.softmax(s, -1), vg)

    o = jax.lax.map(group, (q, k, v)).reshape((-1,) + q.shape[2:])
    return jnp.einsum("hqk,hkd->qd", o, f32(a["wo"], (0, 1)))


def _forward(params, tokens, n_valid, class_ids, *, m, control):
    """Class logits at position ``n_valid - 1`` of ``tokens`` [S]: the
    positions past ``n_valid`` come after it, so neither the causal
    attention nor the recurrence lets them in."""
    def f32(a, in_axes=None):
        return REF.as_f32(a, in_axes, control)
    eps, rm = float(m["rms_norm_eps"]), m["residual_multiplier"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    x = f32(params["embed"]["table"][tokens], (1,)) * m["embedding_multiplier"]
    unit = _period(m["layer_types"])

    def block(x, ps):
        for kind, p in zip(unit, ps):
            h = _rms(x, p["norm1"]["scale"], eps)
            mix = (_mamba(p["mamba"], h, m, f32, eps) if kind == "mamba"
                   else _attention(p["attn"], h, m, f32, causal))
            x = x + rm * mix
            h = _rms(x, p["norm2"]["scale"], eps)
            w = p["mlp"]
            up = jax.nn.silu(h @ f32(w["w1"], (0,))) * (h @ f32(w["w3"], (0,)))
            x = x + rm * (up @ f32(w["w2"], (0,)))
        return x, None

    x, _ = jax.lax.scan(block, x, params["stages"])
    last = _rms(jax.lax.dynamic_index_in_dim(x, n_valid - 1, 0, False),
                params["final_norm"]["scale"], eps)
    return (f32(params["embed"]["table"][class_ids], (1,)) @ last
            ) / m["logits_scaling"]


@functools.lru_cache(maxsize=None)
def _compiled(entry: str, control: bool):
    return jax.jit(functools.partial(_forward, m=json.loads(entry),
                                     control=control))


def class_logits(params, m: Mapping[str, Any], tokens: Sequence[int],
                 n_classes: int, control: bool = False) -> np.ndarray:
    return REF.last_class_logits(
        _compiled(json.dumps(dict(m), sort_keys=True), bool(control)),
        params, tokens, n_classes)


# ------------------------------------------------------------ the work
def _counts(m) -> Tuple[int, int]:
    n_mamba = sum(t == "mamba" for t in m["layer_types"])
    return n_mamba, len(m["layer_types"]) - n_mamba


def linear_flops_per_token(m: Mapping[str, Any]) -> int:
    """Every weight a token multiplies: Mamba in/out projections, q, k,
    v, o on attention layers, SwiGLU on every layer."""
    d, f = m["hidden_size"], m["shared_intermediate_size"]
    H, P = m["mamba_n_heads"], m["mamba_d_head"]
    di = H * P
    q = m["num_attention_heads"] * _head_dim(m)
    kv = m["num_key_value_heads"] * _head_dim(m)
    n_mamba, n_attn = _counts(m)
    mamba = d * (di + _conv_dim(m) + H) + di * d
    attn = d * q + 2 * d * kv + q * d
    return 2 * (n_mamba * mamba + n_attn * attn
                + len(m["layer_types"]) * 3 * d * f)


def recurrent_state_bytes(m: Mapping[str, Any]) -> int:
    """One document's recurrent state: the scan state in float32 and the
    conv window in bf16, over the Mamba layers."""
    H, P, N = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    per = 4 * H * P * N + WK.BF16_BYTES * (m["mamba_d_conv"] - 1) \
        * _conv_dim(m)
    return _counts(m)[0] * per


def scan_work(m: Mapping[str, Any], tokens: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of the selective scan over ``tokens`` new tokens of
    one document, over the Mamba layers: per token and head, the state
    update (decay, outer product, add: 3 H P N) and its read-out (2 H P
    N); bytes are one read of x, B, C (bf16) and dt (f32) and one write
    of y (bf16) a token, and one read and write of the float32 state."""
    H, P, N = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    n_mamba = _counts(m)[0]
    flops = n_mamba * 5 * H * P * N * tokens
    per_tok = WK.BF16_BYTES * (2 * H * P + 2 * N) + 4 * H
    nbytes = n_mamba * (per_tok * tokens + 2 * 4 * H * P * N)
    return flops, nbytes


def attention_work(m: Mapping[str, Any], start: int, stop: int
                   ) -> Tuple[int, int]:
    """(FLOPs, bytes) of attention on the attention layers for queries at
    [start, stop), as ``arch/qwen3.py`` counts them."""
    n_attn = _counts(m)[1]
    h, kv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                 _head_dim(m))
    flops = n_attn * 4 * h * dh * WK.keys_attended(start, stop)
    nbytes = n_attn * WK.BF16_BYTES * (2 * kv * dh * stop
                                       + 2 * h * dh * (stop - start))
    return flops, nbytes


def stage_work(m: Mapping[str, Any], cached: int, doc_tokens: int,
               op_tokens: int, n_classes: int) -> Dict[str, int]:
    """Work of one stage visit of one document (``work.py`` says what is
    counted): projections and SwiGLU for each new token, causal attention
    on the attention layers, the scan on the Mamba layers, the tied head
    over the class rows; ``state_bytes`` is the recurrent state read
    (when the document resumes) and written once."""
    assert 0 <= cached <= doc_tokens and op_tokens >= 0
    stop = doc_tokens + op_tokens
    tokens = stop - cached
    a_flops, a_bytes = attention_work(m, cached, stop)
    s_flops, s_bytes = scan_work(m, tokens)
    head = 2 * m["hidden_size"] * n_classes
    lin = linear_flops_per_token(m) * tokens
    return {"tokens": tokens, "linear_flops": lin, "attn_flops": a_flops,
            "attn_bytes": a_bytes, "head_flops": head,
            "scan_flops": s_flops, "scan_bytes": s_bytes,
            "state_bytes": (1 + (cached > 0)) * recurrent_state_bytes(m),
            "flops": lin + a_flops + s_flops + head}
