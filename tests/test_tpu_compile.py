"""Compile the served kernels for a described TPU v5e, at real widths.

Nothing runs: the TPU compiler, which is installed with libtpu, lowers
each kernel for a chip that is described, not attached, and raises
whatever the chip's compiler would raise (block shapes Mosaic refuses,
scoped-VMEM overflows).  Each test asserts the Mosaic kernel is in the
compiled program (``tpu_custom_call``).  Widths come from the stage
models the chip path serves: llama3.2-1b (32/8 heads, head_dim 64),
qwen3-1.7b (16/8 heads, head_dim 128), for the one-pass operation
suffix the Qwen3-4B and Qwen3-0.6B widths, and granite-4.0-h-micro's
SSD chunk scan and hybrid paged step, in bf16.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every xdist
worker imports this file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import resolve
from repro.configs import get_config, get_reduced
from repro.data.tokenizer import HashWordTokenizer
from repro.kernels import ops
from repro.kernels.decode_attention import (decode_attention_pallas,
                                            paged_decode_attention_pallas)
from repro.kernels.flash_attention import (flash_attention_pallas,
                                           paged_flash_attention_pallas)
from repro.models.model import LM
from repro.models.runtime import Runtime
from repro.serving.engine import LMBackend

MODELS = ("llama3_2_1b", "qwen3_1_7b")


def _heads(arch):
    r = resolve(get_config(arch), tp=1)
    return r.padded_heads, r.padded_kv_heads, r.head_dim


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (arena cache axis S, block_kv): the chip smoke's 256 bucket + 64 op
# reserve (one block), and a 1024 row of two full 512 blocks
@pytest.mark.parametrize("S,block_kv", [(320, 512), (1024, 512)])
@pytest.mark.parametrize("arch", MODELS)
def test_paged_decode_compiles(one_chip, arch, S, block_kv):
    Hq, Hkv, Dh = _heads(arch)
    B, N = 8, 17
    args = (_sds(one_chip, (B, Hq, Dh)),
            _sds(one_chip, (N, S, Hkv, Dh)), _sds(one_chip, (N, S, Hkv, Dh)),
            _sds(one_chip, (B,), jnp.int32), _sds(one_chip, (B,), jnp.int32))

    def fn(q, k, v, slots, kv_len):
        return paged_decode_attention_pallas(q, k, v, slots, kv_len,
                                             block_kv=block_kv)
    assert "tpu_custom_call" in _compile_text(fn, *args)


@pytest.mark.parametrize("arch", MODELS)
def test_paged_decode_block_tables_compile(one_chip, arch):
    Hq, Hkv, Dh = _heads(arch)
    B, N, S, blk = 4, 9, 1024, 512
    args = (_sds(one_chip, (B, Hq, Dh)),
            _sds(one_chip, (N, S, Hkv, Dh)), _sds(one_chip, (N, S, Hkv, Dh)),
            _sds(one_chip, (B, S // blk), jnp.int32),
            _sds(one_chip, (B,), jnp.int32))

    def fn(q, k, v, bt, kv_len):
        return ops.arena_decode_attention(q, k, v, bt[:, 0], kv_len,
                                          block_tables=bt, impl="pallas",
                                          block_kv=blk)
    assert "tpu_custom_call" in _compile_text(fn, *args)


# (arena S_alloc, Sq, kv_valid, q_offset): the served stage shapes —
# a quarter-fraction prefill, the extension to the full 256 bucket, a
# whole-bucket prefill, and a 512-block extension of a 1024 row
@pytest.mark.parametrize("S,Sq,kv_valid,q_offset", [
    (320, 64, 64, 0), (320, 192, 256, 64), (320, 256, 256, 0),
    (1024, 512, 1024, 512)])
@pytest.mark.parametrize("arch", MODELS)
def test_paged_flash_compiles(one_chip, arch, S, Sq, kv_valid, q_offset):
    Hq, Hkv, Dh = _heads(arch)
    B, N = 8, 17
    args = (_sds(one_chip, (B, Hq, Sq, Dh)),
            _sds(one_chip, (N, S, Hkv, Dh)), _sds(one_chip, (N, S, Hkv, Dh)),
            _sds(one_chip, (B,), jnp.int32), _sds(one_chip, (B,), jnp.int32))

    def fn(q, k, v, slots, kv_len):
        return paged_flash_attention_pallas(
            q, k, v, slots, kv_valid=kv_valid, q_offset=q_offset,
            kv_len=kv_len, block_q=512, block_kv=512)
    assert "tpu_custom_call" in _compile_text(fn, *args)


# the one-pass operation suffix of the Qwen3 benchmark pairs: 60 op tokens
# at ragged per-row starts, over the 4B oracle's 2048-bucket arena row
# (2048 + 64 op positions rounded to the 512 block; keys attended up to
# 2048 + 60, padded to 2560) and the 0.6B proxy's 64-bucket row (64 + 64)
@pytest.mark.parametrize("Hq,B,N,S,kv_valid", [
    (32, 2, 3, 2560, 2108),       # Qwen3-4B: 32/8 heads, launches of 2
    (16, 4, 18, 128, 124),        # Qwen3-0.6B: 16/8 heads, launches of 4
])
def test_paged_flash_q_start_compiles(one_chip, Hq, B, N, S, kv_valid):
    Hkv, Dh, Sq = 8, 128, 60
    args = (_sds(one_chip, (B, Sq, Hq, Dh)),
            _sds(one_chip, (N, S, Hkv, Dh)), _sds(one_chip, (N, S, Hkv, Dh)),
            _sds(one_chip, (B,), jnp.int32), _sds(one_chip, (B,), jnp.int32))

    def fn(q, k, v, slots, q_start):
        return ops.attention_paged(
            q, k, v, slots, kv_valid=kv_valid, q_start=q_start,
            kv_len=q_start + Sq, impl="pallas", block_q=512, block_kv=512)
    assert "tpu_custom_call" in _compile_text(fn, *args)


@pytest.mark.parametrize("arch", MODELS)
def test_dense_pair_compiles(one_chip, arch):
    Hq, Hkv, Dh = _heads(arch)
    B, S = 4, 1024
    dec = (_sds(one_chip, (B, Hq, Dh)),
           _sds(one_chip, (B, Hkv, S, Dh)), _sds(one_chip, (B, Hkv, S, Dh)),
           _sds(one_chip, (B,), jnp.int32))
    assert "tpu_custom_call" in _compile_text(
        lambda q, k, v, n: decode_attention_pallas(q, k, v, n), *dec)
    fl = (_sds(one_chip, (B, Hq, S, Dh)),
          _sds(one_chip, (B, Hkv, S, Dh)), _sds(one_chip, (B, Hkv, S, Dh)))
    assert "tpu_custom_call" in _compile_text(
        lambda q, k, v: flash_attention_pallas(q, k, v), *fl)


# the restructurer's chunk embeddings (64 words x 256 dims, f32) and a
# model-width chunk (64 x 2048), both at the VMEM-derived default block_c
@pytest.mark.parametrize("C,T,D", [(40, 64, 256), (256, 64, 2048)])
def test_relevance_score_compiles(one_chip, C, T, D):
    args = (_sds(one_chip, (C, T, D), jnp.float32),
            _sds(one_chip, (C,), jnp.int32),
            _sds(one_chip, (D,), jnp.float32),
            _sds(one_chip, (), jnp.float32))

    def fn(x, lengths, w, b):
        return ops.relevance_score(x, lengths, w, b, impl="pallas")
    assert "tpu_custom_call" in _compile_text(fn, *args)


# a prefill launch (64 new tokens) and a decode-only one (64 cached)
@pytest.mark.parametrize("c_len,n_new", [(0, 64), (64, 0)])
def test_paged_step_runs_op_suffix_as_one_flash_pass(one_chip, c_len, n_new):
    """The served paged step of a full-attention model, compiled for the
    chip: the operation suffix is one ragged-start flash pass, so the
    program holds the paged flash kernel and no paged decode kernel."""
    cfg = get_reduced("qwen3_1_7b", num_layers=2, vocab_size=512)
    model = LM(resolve(cfg, tp=1), Runtime(attn_impl="pallas", remat=False))
    be = LMBackend(name="proxy", model=model, params=None,
                   tokenizer=HashWordTokenizer(512), paged=True)
    B, N, op_len = 4, 5, 7
    s_alloc = be._s_alloc_for(64)
    params = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    arena = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                         model.state_shapes(N, s_alloc))
    i32 = jnp.int32
    step = be._build_step()
    text = step.lower(
        params, arena, _sds(one_chip, (B,), i32),
        _sds(one_chip, (B, n_new), i32), _sds(one_chip, (op_len,), i32),
        _sds(one_chip, (B,), i32), _sds(one_chip, (B,), i32),
        c_len=c_len, op_len=op_len).compile().as_text()
    assert "paged_flash_attention" in text
    assert "paged_decode_attention" not in text


# the oracle's Mamba-2 widths (granite-4.0-h-micro): 64 heads x 64,
# d_state 128, chunk 256, at a 4,096-token document and the 60-token op
@pytest.mark.parametrize("S", [4096, 60])
def test_ssd_chunk_scan_compiles(one_chip, S):
    B, H, P, N = 2, 64, 64, 128
    args = (_sds(one_chip, (B, S, H, P)),
            _sds(one_chip, (B, S, H), jnp.float32),
            _sds(one_chip, (H,), jnp.float32),
            _sds(one_chip, (B, S, N)), _sds(one_chip, (B, S, N)),
            _sds(one_chip, (B, H, P, N), jnp.float32),
            _sds(one_chip, (B,), jnp.int32))

    def fn(*a):
        return ops.ssd_scan(*a, chunk=256, impl="pallas")
    assert "ssd_chunk_scan" in _compile_text(fn, *args)


def test_hybrid_paged_step_compiles(one_chip):
    """The served paged step of granite-4.0-h-micro at its published
    widths (one period of its layer pattern: 9 Mamba-2, 1 attention) and
    the benchmark's oracle launch width (2), for a prefill launch of the
    4,096 bucket: the scan and paged flash kernels are in the program and
    no decode kernel (one op pass)."""
    cfg = get_config("granite_4_0_h_micro")
    cfg = dataclasses.replace(cfg, num_layers=10, vocab_size=512)
    model = LM(resolve(cfg, tp=1), Runtime(attn_impl="pallas", remat=False))
    be = LMBackend(name="oracle", model=model, params=None,
                   tokenizer=HashWordTokenizer(512), paged=True)
    B, N, op_len = 2, 5, 60
    params = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    arena = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                         model.state_shapes(N, be._s_alloc_for(4096)))
    i32 = jnp.int32
    text = be._build_step().lower(
        params, arena, _sds(one_chip, (B,), i32),
        _sds(one_chip, (B, 4096), i32), _sds(one_chip, (op_len,), i32),
        _sds(one_chip, (B,), i32), _sds(one_chip, (B,), i32),
        c_len=0, op_len=op_len).compile().as_text()
    assert "ssd_chunk_scan" in text and "paged_flash_attention" in text
    assert "paged_decode_attention" not in text
