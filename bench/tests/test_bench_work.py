"""Required work counts (bench/work.py and the Qwen3 module's
``stage_work``): hand-checked figures, and invariance to how an
implementation batches, pads or decodes."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import harness  # noqa: E402
import work as WK  # noqa: E402

QWEN3 = harness.load_arch(os.path.dirname(BENCH), "qwen3")

QWEN3_1_7B = {"hidden_size": 2048, "intermediate_size": 6144,
              "num_hidden_layers": 28, "num_attention_heads": 16,
              "num_key_value_heads": 8, "head_dim": 128}


def test_qwen3_1_7b_linear_flops_per_token_hand_checked():
    # per layer: q 2048x2048 + k,v 2 x 2048x1024 + o 2048x2048
    # + SwiGLU 3 x 2048x6144 = 50,331,648 weights; 28 layers, 2 FLOPs each
    assert QWEN3.matmul_params_per_layer(QWEN3_1_7B) == 50_331_648
    assert QWEN3.linear_flops_per_token(QWEN3_1_7B) == 2_818_572_288


def test_qwen3_1_7b_attention_flops_per_token_hand_checked():
    # the token at position p attends p + 1 keys: 28 layers x 4 FLOPs x
    # 16 heads x 128 dims = 229,376 FLOPs a key
    f, _ = QWEN3.attention_work(QWEN3_1_7B, 99, 100)
    assert f == 229_376 * 100


@pytest.mark.parametrize("start,stop", [(0, 1), (0, 2048), (205, 265),
                                        (1500, 1560)])
def test_attention_extend_equals_token_by_token_decode(start, stop):
    """One extend over [start, stop) counts what stop - start decode steps
    count, so serving an op suffix as decode or as extend reads the same."""
    f, _ = QWEN3.attention_work(QWEN3_1_7B, start, stop)
    per_token = sum(QWEN3.attention_work(QWEN3_1_7B, p, p + 1)[0]
                    for p in range(start, stop))
    assert f == per_token


@pytest.mark.parametrize("doc,op", [(1500, 60), (37, 60), (1025, 24)])
def test_op_suffix_as_decode_or_extend(doc, op):
    whole = QWEN3.stage_work(QWEN3_1_7B, 0, doc, op, 2)
    prefix = QWEN3.stage_work(QWEN3_1_7B, 0, doc, 0, 2)
    steps = [QWEN3.stage_work(QWEN3_1_7B, doc + t, doc + t + 1, 0, 2)
             for t in range(op)]
    assert whole["tokens"] == doc + op
    lin = prefix["linear_flops"] + sum(s["linear_flops"] for s in steps)
    att = prefix["attn_flops"] + sum(s["attn_flops"] for s in steps)
    assert (lin, att) == (whole["linear_flops"], whole["attn_flops"])


@pytest.mark.parametrize("bucket,width", [(2048, 1), (2048, 4), (4096, 16)])
def test_counts_do_not_see_bucket_or_launch_width(bucket, width):
    """The count takes true lengths only: a document padded to any bucket,
    in a launch of any width, requires the same work."""
    w = WK.document_work({"proxy": (QWEN3_1_7B, QWEN3)},
                         [("proxy", "o", 1.0)], [0], 1500, {"o": 60}, 2)
    assert w["proxy"]["tokens"] == 1560
    computed = width * (bucket + 60)        # what a padded launch computes
    assert w["proxy"]["tokens"] <= computed


def test_prefix_reused_across_stages_of_one_model():
    models = dict.fromkeys(("proxy", "oracle"), (QWEN3_1_7B, QWEN3))
    stages = [("proxy", "a", 0.25), ("proxy", "b", 1.0), ("oracle", "a", 1.0)]
    w = WK.document_work(models, stages, [0, 1, 2], 1000,
                         {"a": 60, "b": 24}, 2)
    # proxy: 250 + 60, then the other 750 + 24; oracle: all 1000 + 60
    assert w["proxy"]["tokens"] == 250 + 60 + 750 + 24
    assert w["oracle"]["tokens"] == 1060


def test_true_prefix_rounds_up_and_keeps_one_token():
    assert WK.true_prefix(1500, 0.1) == 150
    assert WK.true_prefix(1501, 0.1) == 151
    assert WK.true_prefix(3, 0.1) == 1
