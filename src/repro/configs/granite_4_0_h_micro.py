"""granite-4.0-h-micro: Mamba-2 / GQA hybrid, 9:1.  [hf:ibm-granite/
granite-4.0-h-micro config.json]

40 = 4 x (5 Mamba-2, 1 attention, 4 Mamba-2); Mamba-2 64 heads x 64,
d_state 128, one group, conv 4, chunk 256; attention 32/8 heads x 64 with
no positions and a softmax scale of 1/64; SwiGLU 8,192 on every layer;
multipliers embedding x12, residual x0.22, logits /8; tied 100,352 vocab.
"""
from ..config import ATTN_FULL, HYBRID, MAMBA2, ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family=HYBRID,
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    d_ff=8192,
    vocab_size=100352,
    head_dim=64,
    block_pattern=(MAMBA2,) * 5 + (ATTN_FULL,) + (MAMBA2,) * 4,
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
    use_rope=False,
    ssm_heads=64,
    ssm_head_dim=64,
    ssm_state=128,
    ssm_conv=4,
    ssm_chunk=256,
    max_seq_len=131_072,
    supported_shapes=("train_4k", "prefill_32k", "decode_32k"),
)
