"""Launch-layer units: HLO collective parsing, R-extrapolation arithmetic,
roofline terms, logical param counts, mesh helpers, remesh-compatible specs.
(The heavy 512-device compile path is exercised by the dry-run itself.)
"""
import numpy as np
import pytest

from repro.config import SHAPES, resolve
from repro.configs import ARCHS, get_config
from repro.launch.dryrun import parse_collective_bytes
from repro.launch.roofline import (analytic_memory_floor, analyze,
                                   logical_param_counts, model_flops)

HLO_SNIPPET = """
  %ar = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %x), replica_groups={}
  %ag = bf16[64,64]{1,0} all-gather(bf16[32,64]{1,0} %y), dimensions={0}
  %plain = f32[8,8]{1,0} add(f32[8,8]{1,0} %a, f32[8,8]{1,0} %b)
  %a2a = f32[16]{0} all-to-all(f32[16]{0} %z)
"""


def test_parse_collective_bytes():
    out = parse_collective_bytes(HLO_SNIPPET)
    # output+operand convention: simple AR counts ~2x the payload
    assert out["all-reduce"] == 2 * 128 * 256 * 4
    assert out["all-gather"] == 64 * 64 * 2 + 32 * 64 * 2
    assert out["all-to-all"] == 2 * 16 * 4
    assert "add" not in out and len(out) == 3


def test_extrapolation_identity():
    # A + (R-1)(B-A) must reproduce exact linear costs
    base, body, R = 7.0, 3.0, 10
    a = base + body
    b = base + 2 * body
    assert a + (R - 1) * (b - a) == base + R * body


@pytest.mark.parametrize("arch", ARCHS)
def test_logical_param_counts_in_range(arch):
    """Param counts must land near the arch's advertised size."""
    advertised = {
        "gemma3_27b": 27e9, "minitron_4b": 4e9, "qwen3_1_7b": 1.7e9,
        "llama3_2_1b": 1.2e9, "qwen2_vl_2b": 1.5e9, "phi3_5_moe": 42e9,
        "dbrx_132b": 132e9, "whisper_base": 72e6, "xlstm_350m": 350e6,
        "recurrentgemma_2b": 2.7e9, "granite_4_0_h_micro": 3.2e9,
    }[arch]
    n = logical_param_counts(arch)["total"]
    assert 0.3 * advertised < n < 3.0 * advertised, (arch, n)


def test_moe_active_less_than_total():
    c = logical_param_counts("dbrx_132b")
    assert c["active"] < 0.5 * c["total"]


@pytest.mark.parametrize("arch,shape", [
    ("llama3_2_1b", "train_4k"), ("gemma3_27b", "prefill_32k"),
    ("gemma3_27b", "long_500k"), ("dbrx_132b", "decode_32k")])
def test_memory_floor_positive_and_sane(arch, shape):
    floor = analytic_memory_floor(arch, shape, 256)
    assert floor > 0
    # per-chip floor must be below HBM-feasible per-step traffic at 1 Hz
    assert floor < 1e13


def test_model_flops_train_is_6nd():
    mf = model_flops("llama3_2_1b", "train_4k")
    n = logical_param_counts("llama3_2_1b")["active"]
    d = SHAPES["train_4k"].global_batch * SHAPES["train_4k"].seq_len
    assert mf == pytest.approx(6 * n * d)


def test_analyze_handles_failed_and_good_cells():
    assert analyze({"ok": False}) is None
    row = analyze({
        "ok": True, "arch": "llama3_2_1b", "shape": "train_4k",
        "mesh": "single", "devices": 256,
        "flops": 3.3e13, "bytes_accessed": 4.1e12,
        "collective_bytes": {"all-reduce": 1e10},
        "extrapolated": {"flops": 3.3e13, "bytes_accessed": 4.1e12,
                         "collective_bytes": {"all-reduce": 1e10}},
    })
    assert row.dominant in ("compute", "memory", "collective")
    assert 0 < row.useful_ratio < 2
    assert row.memory_s <= row.memory_hlo_s


def test_all_configs_resolve_for_tp16():
    """Padding policy must produce TP-clean dims for every arch."""
    for arch in ARCHS:
        cfg = get_config(arch)
        r = resolve(cfg, tp=16)
        assert r.padded_heads % 16 == 0 or r.padded_heads < 16
        assert r.padded_vocab % 16 == 0
        if cfg.pad_kv_to_tp or cfg.num_kv_heads >= 16:
            assert r.padded_kv_heads % 16 == 0
        assert r.padded_heads % r.padded_kv_heads == 0


def test_supported_shapes_follow_assignment_rules():
    from repro.config import ATTN_FULL
    for arch in ARCHS:
        cfg = get_config(arch)
        kinds = set(cfg.layer_kinds())
        pure_full_attn = kinds == {ATTN_FULL}
        if "long_500k" in cfg.supported_shapes:
            assert not pure_full_attn, f"{arch} must skip long_500k"
        assert "train_4k" in cfg.supported_shapes
        assert "decode_32k" in cfg.supported_shapes   # all archs decode


def test_compile_cache_dir_env_wins_else_fixed_checkout_path(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR is left to JAX; without it the cache sits
    at the checkout's fixed .jax_cache (a moving path would never hit)."""
    import os

    import jax

    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(compile_cache.CHECKOUT, ".jax_cache")
        assert os.path.isfile(os.path.join(compile_cache.CHECKOUT, "src",
                                           "repro", "launch", "serve.py"))
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path     # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_unresolved_docs_lists_every_non_resolved_terminal():
    """The serve entry point exits non-zero on any of these."""
    from types import SimpleNamespace

    from repro.launch.serve import unresolved_docs
    results = {0: SimpleNamespace(status={3: "resolved", 1: "failed"}),
               1: SimpleNamespace(status={0: "timed_out", 2: "resolved"})}
    assert unresolved_docs(results) == [(0, 1, "failed"),
                                        (1, 0, "timed_out")]
    assert unresolved_docs({0: SimpleNamespace(status={5: "resolved"})}) \
        == []
