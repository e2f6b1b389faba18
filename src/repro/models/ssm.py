"""Recurrent sequence mixers: xLSTM (mLSTM + sLSTM) and RG-LRU (Griffin).

TPU adaptation notes (DESIGN.md §2.1): the reference CUDA kernels for these
blocks are replaced with MXU-friendly formulations —

* **mLSTM** runs in *chunkwise-parallel* form: within a chunk of L tokens the
  Gram matrix / decay matrix math is dense [L, L] einsums (MXU work); across
  chunks a short ``lax.scan`` carries the (C, n, m) matrix-memory state.
  This is the TPU analogue of the xLSTM "chunkwise" CUDA kernel, validated
  against the sequential recurrence in tests.
* **sLSTM** has a true nonlinear recurrence (h_{t-1} enters the gate
  pre-activations), so it cannot be parallelized over time; we scan with a
  per-head block-diagonal recurrent matrix.  This sequential scan is a
  property of the architecture, not the port.
* **RG-LRU** is a gated *linear* recurrence -> ``jax.lax.associative_scan``
  (log-depth parallel scan), plus a width-4 depthwise conv with carried
  state for decode.

All mixers expose (train/full, step) entry points with explicit state
pytrees so the serving engine can stream documents through cascades.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import _dense_init

LOG_EPS = -30.0


# ===========================================================================
# mLSTM
# ===========================================================================

def init_mlstm(rng, d: int, heads: int, dtype=jnp.bfloat16):
    ks = jax.random.split(rng, 8)
    return {
        "wq": _dense_init(ks[0], (d, d), d, dtype),
        "wk": _dense_init(ks[1], (d, d), d, dtype),
        "wv": _dense_init(ks[2], (d, d), d, dtype),
        "wi": _dense_init(ks[3], (d, heads), d, jnp.float32),
        "wf": _dense_init(ks[4], (d, heads), d, jnp.float32),
        "wo": _dense_init(ks[5], (d, d), d, dtype),
        "wz": _dense_init(ks[6], (d, d), d, dtype),     # gate branch
        "wd": _dense_init(ks[7], (d, d), d, dtype),     # down proj
        "bf": jnp.ones((heads,), jnp.float32) * 2.0,    # forget bias -> long memory
        "bi": jnp.zeros((heads,), jnp.float32),
    }


def spec_mlstm():
    return {
        "wq": (None, "tp"), "wk": (None, "tp"), "wv": (None, "tp"),
        "wi": (None, None), "wf": (None, None),
        "wo": (None, "tp"), "wz": (None, "tp"), "wd": ("tp", None),
        "bf": (None,), "bi": (None,),
    }


def init_mlstm_state(batch: int, heads: int, dh: int, dtype=jnp.float32):
    return {
        "C": jnp.zeros((batch, heads, dh, dh), dtype),   # matrix memory [dv, dk]
        "n": jnp.zeros((batch, heads, dh), dtype),
        "m": jnp.full((batch, heads), LOG_EPS, dtype),
    }


def mlstm_state_shape(batch: int, heads: int, dh: int):
    return {
        "C": jax.ShapeDtypeStruct((batch, heads, dh, dh), jnp.float32),
        "n": jax.ShapeDtypeStruct((batch, heads, dh), jnp.float32),
        "m": jax.ShapeDtypeStruct((batch, heads), jnp.float32),
    }


def spec_mlstm_state():
    # dv (C dim 2) sharded over model: heads (4) < tp, so shard inner dim
    return {"C": ("dp", None, "tp", None), "n": ("dp", None, "tp"),
            "m": ("dp", None)}


def _mlstm_gates(p, x):
    """x: [B, T, D] -> (q,k,v [B,T,H,dh], li/lf [B,T,H] log gates, o,z)."""
    B, T, D = x.shape
    H = p["wi"].shape[1]
    dh = D // H
    q = (x @ p["wq"]).reshape(B, T, H, dh)
    k = (x @ p["wk"]).reshape(B, T, H, dh) * (dh ** -0.5)
    v = (x @ p["wv"]).reshape(B, T, H, dh)
    li = x.astype(jnp.float32) @ p["wi"] + p["bi"]          # input gate pre-act
    lf = jax.nn.log_sigmoid(x.astype(jnp.float32) @ p["wf"] + p["bf"])
    o = jax.nn.sigmoid(x @ p["wo"])
    z = jax.nn.silu(x @ p["wz"])
    return q, k, v, li, lf, o, z


def mlstm_chunk(q, k, v, li, lf, state, chunk: int):
    """Chunkwise-parallel mLSTM core.

    q/k/v: [B, T, H, dh]; li/lf: [B, T, H]; state from init_mlstm_state.
    Returns (h [B, T, H, dh], new state).  T must be a multiple of chunk.
    """
    B, T, H, dh = q.shape
    L = min(chunk, T)
    assert T % L == 0, (T, L)
    nc = T // L

    def resh(x):
        return jnp.moveaxis(
            x.reshape(B, nc, L, H, -1).squeeze(-1)
            if x.ndim == 3 else x.reshape(B, nc, L, H, dh), 1, 0)

    qc = jnp.moveaxis(q.reshape(B, nc, L, H, dh), 1, 0)     # [nc,B,L,H,dh]
    kc = jnp.moveaxis(k.reshape(B, nc, L, H, dh), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nc, L, H, dh), 1, 0)
    lic = jnp.moveaxis(li.reshape(B, nc, L, H), 1, 0)       # [nc,B,L,H]
    lfc = jnp.moveaxis(lf.reshape(B, nc, L, H), 1, 0)

    tri = jnp.tril(jnp.ones((L, L), bool))                  # j <= i

    def step(carry, xs):
        C, n, m = carry                                     # [B,H,dh,dh],[B,H,dh],[B,H]
        qb, kb, vb, lib, lfb = xs
        qf = qb.astype(jnp.float32)
        kf = kb.astype(jnp.float32)
        vf = vb.astype(jnp.float32)
        a = jnp.cumsum(lfb, axis=1)                         # [B,L,H] inclusive
        A = a[:, -1]                                        # [B,H]
        # intra-chunk log weights S[b,h,i,j] = a_i - a_j + li_j  (j <= i)
        S = (a[:, :, None, :] - a[:, None, :, :]
             + lib[:, None, :, :])                          # [B,i,j,H]
        S = jnp.moveaxis(S, 3, 1)                           # [B,H,i,j]
        S = jnp.where(tri[None, None], S, -jnp.inf)
        inter = m[:, :, None] + jnp.moveaxis(a, 2, 1)       # [B,H,i]
        m_i = jnp.maximum(jnp.max(S, axis=-1), inter)       # [B,H,i]
        m_i = jnp.maximum(m_i, LOG_EPS)
        w_intra = jnp.exp(S - m_i[..., None])               # [B,H,i,j]
        w_inter = jnp.exp(inter - m_i)                      # [B,H,i]
        gram = jnp.einsum("blhd,bjhd->bhlj", qf, kf)        # [B,H,i,j]
        num = jnp.einsum("bhij,bjhd->bihd", w_intra * gram, vf) \
            + jnp.einsum("bhi,bhde,bihe->bihd", w_inter, C, qf)
        nvec = jnp.einsum("bhij,bjhd->bihd", w_intra, kf) \
            + w_inter[..., None].transpose(0, 2, 1, 3) * n[:, None]
        qn = jnp.einsum("bihd,bihd->bih", nvec, qf)         # [B,i,H]
        denom = jnp.maximum(jnp.abs(qn), jnp.exp(-m_i).transpose(0, 2, 1))
        h = num / denom[..., None]                          # [B,L,H,dh]

        # end-of-chunk state
        wj = (A[:, None] - a) + lib                         # [B,L,H] log weight of input j
        m_new = jnp.maximum(m + A, jnp.max(wj, axis=1))     # [B,H]
        m_new = jnp.maximum(m_new, LOG_EPS)
        carryw = jnp.exp(m + A - m_new)                     # [B,H]
        inpw = jnp.exp(wj - m_new[:, None])                 # [B,L,H]
        C_new = carryw[..., None, None] * C + \
            jnp.einsum("blh,blhd,blhe->bhde", inpw, vf, kf)
        n_new = carryw[..., None] * n + \
            jnp.einsum("blh,blhd->bhd", inpw, kf)
        return (C_new, n_new, m_new), h

    (C, n, m), hs = jax.lax.scan(
        step, (state["C"], state["n"], state["m"]), (qc, kc, vc, lic, lfc))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, T, H, dh)
    return h, {"C": C, "n": n, "m": m}


def mlstm_recurrent_ref(q, k, v, li, lf, state):
    """Sequential recurrence — the correctness oracle for mlstm_chunk."""
    B, T, H, dh = q.shape

    def step(carry, xs):
        C, n, m = carry
        qt, kt, vt, lit, lft = xs                           # [B,H,dh],[B,H]
        m_new = jnp.maximum(lft + m, lit)
        m_new = jnp.maximum(m_new, LOG_EPS)
        fw = jnp.exp(lft + m - m_new)
        iw = jnp.exp(lit - m_new)
        C = fw[..., None, None] * C + iw[..., None, None] * \
            jnp.einsum("bhd,bhe->bhde", vt, kt)
        n = fw[..., None] * n + iw[..., None] * kt
        qn = jnp.einsum("bhd,bhd->bh", n, qt)
        denom = jnp.maximum(jnp.abs(qn), jnp.exp(-m_new))
        h = jnp.einsum("bhde,bhe->bhd", C, qt) / denom[..., None]
        return (C, n, m_new), h

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in
               (q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), li, lf))
    (C, n, m), hs = jax.lax.scan(step, (state["C"], state["n"], state["m"]), xs)
    return jnp.moveaxis(hs, 0, 1), {"C": C, "n": n, "m": m}


def mlstm_apply(p, x, *, state=None, mode: str = "full", chunk: int = 256,
                heads: int = 4):
    """Full mLSTM block: gates + core + output gating + down-proj.

    mode "full": x [B, T, D]; mode "step": x [B, 1, D] with state.
    Returns (y [B, T, D], new_state).
    """
    B, T, D = x.shape
    if state is None:
        state = init_mlstm_state(B, heads, D // heads)
    q, k, v, li, lf, o, z = _mlstm_gates(p, x)
    if mode == "step":
        h, new_state = mlstm_recurrent_ref(q, k, v, li, lf, state)
    else:
        h, new_state = mlstm_chunk(q, k, v, li, lf, state, chunk)
    h = h.reshape(B, T, D).astype(x.dtype) * o
    y = (h * z) @ p["wd"]
    return y, new_state


# ===========================================================================
# sLSTM
# ===========================================================================

def init_slstm(rng, d: int, heads: int, dtype=jnp.bfloat16):
    ks = jax.random.split(rng, 4)
    dh = d // heads
    w = _dense_init(ks[0], (d, 4 * d), d, dtype)
    r = (jax.random.normal(ks[1], (4, heads, dh, dh), jnp.float32)
         * (1.0 / math.sqrt(dh))).astype(jnp.float32)
    return {
        "w": w,                                  # x -> (z,i,f,o) pre-acts
        "r": r,                                  # recurrent block-diag per head
        "b": jnp.concatenate([
            jnp.zeros((d,), jnp.float32),
            jnp.zeros((d,), jnp.float32),
            jnp.ones((d,), jnp.float32) * 2.0,   # forget bias
            jnp.zeros((d,), jnp.float32)]),
        "wo": _dense_init(ks[2], (d, d), d, dtype),
        "wd": _dense_init(ks[3], (d, d), d, dtype),
    }


def spec_slstm():
    return {"w": (None, "tp"), "r": (None, None, None, None), "b": (None,),
            "wo": (None, "tp"), "wd": ("tp", None)}


def init_slstm_state(batch: int, d: int, dtype=jnp.float32):
    return {
        "c": jnp.zeros((batch, d), dtype),
        "n": jnp.full((batch, d), 1e-6, dtype),
        "h": jnp.zeros((batch, d), dtype),
        "m": jnp.full((batch, d), LOG_EPS, dtype),
    }


def slstm_state_shape(batch: int, d: int):
    return {k: jax.ShapeDtypeStruct((batch, d), jnp.float32)
            for k in ("c", "n", "h", "m")}


def spec_slstm_state():
    return {k: ("dp", "tp") for k in ("c", "n", "h", "m")}


def slstm_apply(p, x, *, state=None, heads: int = 4, mode: str = "full"):
    """sLSTM block. x: [B, T, D]. Sequential over T (true recurrence)."""
    B, T, D = x.shape
    dh = D // heads
    if state is None:
        state = init_slstm_state(B, D)
    pre = (x @ p["w"]).astype(jnp.float32) + p["b"]         # [B, T, 4D]
    pre = jnp.moveaxis(pre.reshape(B, T, 4, D), 1, 0)       # [T, B, 4, D]

    r = p["r"]                                              # [4, H, dh, dh]

    def step(carry, xs):
        c, n, h, m = carry
        # recurrent contribution: h grouped per head
        hh = h.reshape(B, heads, dh)
        rec = jnp.einsum("bhd,ghde->bghe", hh, r).reshape(B, 4, D)
        zp, ip, fp, op = [xs[:, g] + rec[:, g] for g in range(4)]
        z = jnp.tanh(zp)
        o = jax.nn.sigmoid(op)
        lf = jax.nn.log_sigmoid(fp)
        m_new = jnp.maximum(lf + m, ip)
        m_new = jnp.maximum(m_new, LOG_EPS)
        fw = jnp.exp(lf + m - m_new)
        iw = jnp.exp(ip - m_new)
        c = fw * c + iw * z
        n = fw * n + iw
        h_new = o * c / jnp.maximum(n, 1e-6)
        return (c, n, h_new, m_new), h_new

    (c, n, h, m), hs = jax.lax.scan(
        step, (state["c"], state["n"], state["h"], state["m"]), pre)
    y = jnp.moveaxis(hs, 0, 1).astype(x.dtype)              # [B, T, D]
    y = jax.nn.sigmoid(x @ p["wo"]) * y
    y = y @ p["wd"]
    return y, {"c": c, "n": n, "h": h, "m": m}


# ===========================================================================
# RG-LRU (Griffin / RecurrentGemma recurrent block)
# ===========================================================================

RGLRU_C = 8.0
CONV_WIDTH = 4


def init_rglru(rng, d: int, d_rnn: int, dtype=jnp.bfloat16):
    ks = jax.random.split(rng, 6)
    # Lambda init so a = exp(-8*softplus(L)*r) spans slow/fast decay
    u = jax.random.uniform(ks[4], (d_rnn,), jnp.float32, 0.9, 0.999)
    lam = jnp.log(jnp.expm1(-jnp.log(u) / RGLRU_C))         # softplus^-1
    return {
        "w_in": _dense_init(ks[0], (d, d_rnn), d, dtype),
        "w_gate": _dense_init(ks[1], (d, d_rnn), d, dtype),
        "conv": (jax.random.normal(ks[2], (CONV_WIDTH, d_rnn), jnp.float32)
                 * 0.1).astype(dtype),
        "conv_b": jnp.zeros((d_rnn,), dtype),
        "w_r": _dense_init(ks[3], (d_rnn, d_rnn), d_rnn, dtype),
        "w_i": _dense_init(ks[5], (d_rnn, d_rnn), d_rnn, dtype),
        "b_r": jnp.zeros((d_rnn,), jnp.float32),
        "b_i": jnp.zeros((d_rnn,), jnp.float32),
        "lam": lam,
        "w_out": _dense_init(jax.random.fold_in(rng, 7), (d_rnn, d), d_rnn, dtype),
    }


def spec_rglru():
    return {
        "w_in": (None, "tp"), "w_gate": (None, "tp"),
        "conv": (None, "tp"), "conv_b": ("tp",),
        "w_r": (None, "tp"), "w_i": (None, "tp"),
        "b_r": ("tp",), "b_i": ("tp",), "lam": ("tp",),
        "w_out": ("tp", None),
    }


def init_rglru_state(batch: int, d_rnn: int, dtype=jnp.float32):
    return {
        "h": jnp.zeros((batch, d_rnn), dtype),
        "conv": jnp.zeros((batch, CONV_WIDTH - 1, d_rnn), dtype),
    }


def rglru_state_shape(batch: int, d_rnn: int):
    return {
        "h": jax.ShapeDtypeStruct((batch, d_rnn), jnp.float32),
        "conv": jax.ShapeDtypeStruct((batch, CONV_WIDTH - 1, d_rnn), jnp.float32),
    }


def spec_rglru_state():
    return {"h": ("dp", "tp"), "conv": ("dp", None, "tp")}


def _causal_conv(xi, conv_w, conv_b, conv_state):
    """Depthwise causal conv, width 4. xi: [B, T, d_rnn]."""
    B, T, dr = xi.shape
    hist = jnp.concatenate([conv_state, xi.astype(jnp.float32)], axis=1)
    out = jnp.zeros((B, T, dr), jnp.float32)
    for w in range(CONV_WIDTH):
        out = out + hist[:, w:w + T] * conv_w[w].astype(jnp.float32)
    new_state = hist[:, -(CONV_WIDTH - 1):]
    return out + conv_b.astype(jnp.float32), new_state


def rglru_apply(p, x, *, state=None, mode: str = "full"):
    """Griffin recurrent block. x: [B, T, D] -> ([B, T, D], state)."""
    B, T, D = x.shape
    dr = p["w_in"].shape[1]
    if state is None:
        state = init_rglru_state(B, dr)
    gate = jax.nn.gelu((x @ p["w_gate"]).astype(jnp.float32))
    xi = x @ p["w_in"]
    xi, conv_state = _causal_conv(xi, p["conv"], p["conv_b"], state["conv"])

    r = jax.nn.sigmoid(xi @ p["w_r"].astype(jnp.float32) + p["b_r"])
    i = jax.nn.sigmoid(xi @ p["w_i"].astype(jnp.float32) + p["b_i"])
    log_a = -RGLRU_C * jax.nn.softplus(p["lam"]) * r         # [B, T, dr]
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * xi)

    if T == 1:
        h = a[:, 0] * state["h"] + gated[:, 0]
        hs = h[:, None]
    else:
        # associative scan over time: pairs (a_t, b_t); include carry by
        # folding the initial state into the first step.
        b0 = gated.at[:, 0].add(a[:, 0] * state["h"])

        def combine(l, r_):
            al, bl = l
            ar, br = r_
            return al * ar, ar * bl + br

        _, hs = jax.lax.associative_scan(combine, (a, b0), axis=1)
        h = hs[:, -1]

    y = (hs * gate).astype(x.dtype) @ p["w_out"]
    return y, {"h": h, "conv": conv_state}


# ===========================================================================
# Mamba-2 (SSD)
# ===========================================================================
#
# One layer (Dao & Gu 2024, arXiv:2405.21060; Hugging Face ``Mamba2Mixer``
# with one group): in_proj -> z | xBC | dt; a width-K depthwise causal
# conv with bias over xBC, SiLU; x, B, C split from it; dt = softplus(dt
# + dt_bias), A = -exp(A_log); the selective scan (``kernels.ops.
# ssd_scan``) plus D * x; y * SiLU(z) through an RMSNorm over d_inner;
# out_proj.  The serve state is the conv's last K-1 inputs and the scan
# state [H, P, N] in float32.

def mamba2_sizes(p) -> Tuple[int, int, int, int]:
    """(heads, head_dim, d_state, conv width) from a layer's params."""
    H = p["A_log"].shape[-1]
    d_inner = p["out_proj"].shape[-2]
    conv_dim = p["conv_w"].shape[-1]
    return H, d_inner // H, (conv_dim - d_inner) // 2, p["conv_w"].shape[-2]


def init_mamba2(rng, d: int, heads: int, head_dim: int, d_state: int,
                conv: int, dtype=jnp.bfloat16):
    """Mamba-2's published initialisation: A in [1, 16], dt in [0.001,
    0.1] (log-uniform, stored as softplus^-1), D = 1."""
    ks = jax.random.split(rng, 5)
    d_inner = heads * head_dim
    conv_dim = d_inner + 2 * d_state
    dt = jnp.exp(jax.random.uniform(ks[2], (heads,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        "in_proj": _dense_init(ks[0], (d, d_inner + conv_dim + heads), d,
                               dtype),
        "conv_w": (jax.random.normal(ks[1], (conv, conv_dim), jnp.float32)
                   * (1.0 / math.sqrt(conv))).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(ks[3], (heads,), jnp.float32,
                                            1.0, 16.0)),
        "D": jnp.ones((heads,), jnp.float32),
        "norm": {"scale": jnp.ones((d_inner,), jnp.float32)},
        "out_proj": _dense_init(ks[4], (d_inner, d), d_inner, dtype),
    }


def spec_mamba2():
    return {"in_proj": (None, None), "conv_w": (None, None),
            "conv_b": (None,), "dt_bias": (None,), "A_log": (None,),
            "D": (None,), "norm": {"scale": (None,)},
            "out_proj": (None, None)}


def mamba2_state_shape(batch: int, heads: int, head_dim: int, d_state: int,
                       conv: int, dtype=jnp.bfloat16):
    return {
        "conv": jax.ShapeDtypeStruct(
            (batch, conv - 1, heads * head_dim + 2 * d_state), dtype),
        "ssm": jax.ShapeDtypeStruct((batch, heads, head_dim, d_state),
                                    jnp.float32),
    }


def init_mamba2_state(batch: int, heads: int, head_dim: int, d_state: int,
                      conv: int, dtype=jnp.bfloat16):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        mamba2_state_shape(batch, heads, head_dim, d_state,
                                           conv, dtype))


def spec_mamba2_state():
    return {"conv": ("dp", None, None), "ssm": ("dp", None, None, None)}


def mamba2_apply(p, x, *, state, mode: str = "full",
                 n_true: Optional[jnp.ndarray] = None, chunk: int = 256,
                 impl: str = "xla", eps: float = 1e-5):
    """Mamba-2 mixer over ``x`` [B, T, D], resumed from ``state``.

    mode "full": the chunked scan; ``n_true`` [B] (None: all T) is each
    row's true tokens in this chunk: dt is 0 past it and the conv state
    is taken from the last true inputs, so bucket PAD never enters the
    state.  mode "step": T == 1, the one-token recurrence in jnp.
    Returns (y [B, T, D], new state)."""
    from ..kernels import ops          # deferred: keep the import DAG flat
    B, T, _ = x.shape
    H, P, N, K = mamba2_sizes(p)
    d_inner = H * P
    f32 = jnp.float32
    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:2 * d_inner + 2 * N]
    dt = jax.nn.softplus(zxbcdt[..., 2 * d_inner + 2 * N:].astype(f32)
                         + p["dt_bias"])                       # [B, T, H]
    hist = jnp.concatenate([state["conv"].astype(f32), xbc.astype(f32)], 1)
    w = p["conv_w"].astype(f32)
    conv = p["conv_b"].astype(f32) + sum(hist[:, k:k + T] * w[k]
                                         for k in range(K))
    if n_true is None:
        conv_state = hist[:, T:]
    else:
        idx = n_true.astype(jnp.int32)[:, None] + jnp.arange(K - 1)[None]
        conv_state = jnp.take_along_axis(hist, idx[..., None], axis=1)
    xbc = jax.nn.silu(conv).astype(x.dtype)
    xs = xbc[..., :d_inner].reshape(B, T, H, P)
    bm, cm = xbc[..., d_inner:d_inner + N], xbc[..., d_inner + N:]
    A = -jnp.exp(p["A_log"])
    h0 = state["ssm"]
    if mode == "step":
        dA = jnp.exp(dt[:, 0] * A)                             # [B, H]
        h = (dA[..., None, None] * h0
             + (dt[:, 0, :, None] * xs[:, 0].astype(f32))[..., None]
             * bm[:, 0, None, None, :].astype(f32))
        y = jnp.einsum("bhpn,bn->bhp", h, cm[:, 0].astype(f32))[:, None]
    else:
        length = (jnp.full((B,), T, jnp.int32) if n_true is None
                  else n_true.astype(jnp.int32))
        y, h = ops.ssd_scan(xs, dt, A, bm, cm, h0, length, chunk=chunk,
                            impl=impl)
    y = y.astype(f32) + p["D"][:, None] * xs.astype(f32)
    y = y.reshape(B, T, d_inner) * jax.nn.silu(z.astype(f32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps) \
        * p["norm"]["scale"]
    out = y.astype(x.dtype) @ p["out_proj"]
    return out, {"conv": conv_state.astype(state["conv"].dtype), "ssm": h}


def mamba2_recurrent_ref(p, x, state, eps: float = 1e-5):
    """The mixer one token at a time in float32 (parameters, input and
    state cast up): the sequential recurrence the chunked scan is tested
    against.  -> (y [B, T, D] float32, state)."""
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    st = jax.tree.map(lambda a: a.astype(jnp.float32), state)

    def step(st, xt):
        y, st = mamba2_apply(p32, xt[:, None], state=st, mode="step",
                             eps=eps)
        return st, y[:, 0]

    st, ys = jax.lax.scan(step, st, jnp.moveaxis(x.astype(jnp.float32), 1, 0))
    return jnp.moveaxis(ys, 0, 1), st
