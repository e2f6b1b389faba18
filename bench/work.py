"""The work a cascade stage requires, counted from shapes and true lengths.

What is counted is what any implementation has to do to answer the
stage: every true document token that is new to the model, and every
operation token, goes once through every layer; each of those tokens
attends once to the keys before it; the head is read at the last
position over the class rows only.  Bucket padding, launch-width
padding, re-reading the weights once per decoded token and a prefix
computed twice are not counted, so a share of a peak built on these
counts reads the same work whatever implements it, and cannot pass 100%
by a change of implementation.

A model is described by the published config keys (``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``intermediate_size``).
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Mapping, Sequence, Tuple

BF16_BYTES = 2


def matmul_params_per_layer(m: Mapping[str, int]) -> int:
    """Weights every token multiplies per layer: q, k, v, o and SwiGLU."""
    d, f = m["hidden_size"], m["intermediate_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def linear_flops_per_token(m: Mapping[str, int]) -> int:
    return 2 * m["num_hidden_layers"] * matmul_params_per_layer(m)


def _keys_attended(start: int, stop: int) -> int:
    """Sum over query positions p in [start, stop) of the p + 1 keys each
    sees under a causal mask."""
    return (stop * (stop + 1) - start * (start + 1)) // 2


def attention_work(m: Mapping[str, int], start: int, stop: int
                   ) -> Tuple[int, int]:
    """(FLOPs, bytes) of attention for queries at positions [start, stop)
    over a causal prefix: QK^T and PV at 2 FLOPs a multiply-add; bytes are
    one read of every key and value up to ``stop`` and one read of Q and
    write of O per query, per layer, in bf16."""
    L, dh = m["num_hidden_layers"], m["head_dim"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    flops = L * 4 * h * dh * _keys_attended(start, stop)
    nbytes = L * BF16_BYTES * (2 * kv * dh * stop
                               + 2 * h * dh * (stop - start))
    return flops, nbytes


def stage_work(m: Mapping[str, int], cached: int, doc_tokens: int,
               op_tokens: int, n_classes: int) -> Dict[str, int]:
    """Work of one stage visit of one document.

    ``doc_tokens`` is the true document prefix the stage reads (its
    fraction of the document), ``cached`` the part of it this model has
    already processed at an earlier stage (reused, so not counted again),
    ``op_tokens`` the operation that follows the prefix."""
    assert 0 <= cached <= doc_tokens and op_tokens >= 0
    stop = doc_tokens + op_tokens
    tokens = stop - cached
    a_flops, a_bytes = attention_work(m, cached, stop)
    head = 2 * m["hidden_size"] * n_classes
    lin = linear_flops_per_token(m) * tokens
    return {"tokens": tokens, "linear_flops": lin, "attn_flops": a_flops,
            "attn_bytes": a_bytes, "head_flops": head,
            "flops": lin + a_flops + head}


def true_prefix(doc_len: int, fraction: float) -> int:
    """True tokens of a document that a stage at ``fraction`` reads."""
    return max(int(math.ceil(doc_len * fraction)), 1)


def document_work(models: Mapping[str, Mapping[str, int]],
                  stages: Sequence[Tuple[str, str, float]],
                  visited: Iterable[int], doc_len: int,
                  op_tokens: Mapping[str, int], n_classes: int
                  ) -> Dict[str, Dict[str, int]]:
    """Work of one document's visits to ``stages[i]`` for i in ``visited``
    (in order), summed per model.  A later stage on the same model reuses
    the prefix an earlier one read."""
    seen: Dict[str, int] = {}
    out: Dict[str, Dict[str, int]] = {}
    for i in visited:
        model, op, fraction = stages[i]
        n = true_prefix(doc_len, fraction)
        cached = min(seen.get(model, 0), n)
        w = stage_work(models[model], cached, n, op_tokens[op], n_classes)
        seen[model] = max(seen.get(model, 0), n)
        acc = out.setdefault(model, {})
        for k, v in w.items():
            acc[k] = acc.get(k, 0) + v
    return out
