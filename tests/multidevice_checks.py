"""Multi-device correctness checks (run under 8 forced host devices).

Invoked by tests/test_multidevice.py in a subprocess so the main pytest
process keeps its single real CPU device.  Prints one "PASS <name>" line
per check; any exception fails the subprocess.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.distributed.collectives import (compressed_psum,            # noqa: E402
                                           matmul_ag_overlap,
                                           ring_all_gather,
                                           ring_reduce_scatter,
                                           sp_decode_attention)
from repro.distributed.compat import shard_map  # noqa: E402
from repro.kernels import ref   # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

assert len(jax.devices()) == 8

mesh = make_mesh((4, 2), ("data", "model"))


def check_ring_all_gather():
    x = jnp.arange(32.0).reshape(8, 4)

    def body(xl):
        return ring_all_gather(xl, "data", axis=0)

    out = shard_map(body, mesh=mesh, in_specs=P("data", None),
                        out_specs=P("data", None))(x)
    # every shard holds the full concat -> output tiled 4x along axis 0
    out_np = np.asarray(out)
    np.testing.assert_allclose(out_np[:8], np.asarray(x))
    print("PASS ring_all_gather")


def check_ring_reduce_scatter():
    x = jnp.arange(64.0).reshape(8, 8)

    def body(xl):
        return ring_reduce_scatter(xl, "model", axis=1)

    out = shard_map(body, mesh=mesh, in_specs=P(None, "model"),
                        out_specs=P(None, "model"))(x)
    # reference: reduce over model shards then scatter along axis 1
    a, b = np.asarray(x)[:, :4], np.asarray(x)[:, 4:]
    ref_rs = a + b              # each half reduces to the same sum
    out_np = np.asarray(out)
    # shard 0 holds chunk 0 of the sum, shard 1 chunk 1
    np.testing.assert_allclose(out_np[:, :2], ref_rs[:, :2])
    np.testing.assert_allclose(out_np[:, 2:4]. T.T, ref_rs[:, 2:4])
    print("PASS ring_reduce_scatter")


def check_sp_decode_attention():
    B, H, KV, S, Dh = 1, 4, 2, 64, 8
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, Dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, Dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, Dh))
    kv_len = jnp.asarray([40], jnp.int32)
    out = sp_decode_attention(q, k, v, kv_len, mesh=mesh,
                              sm_scale=Dh ** -0.5, axis="data")
    expected = ref.decode_reference(q, k, v, kv_len=kv_len,
                                    sm_scale=Dh ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=1e-5, rtol=1e-4)
    print("PASS sp_decode_attention")


def check_compressed_psum():
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 16))

    def body(xl):
        red, err = compressed_psum(xl, "data")
        return red

    out = shard_map(body, mesh=mesh, in_specs=P("data", None),
                        out_specs=P("data", None))(x)
    # reference: mean over the 4 data shards
    ref_mean = np.asarray(x).reshape(4, 2, 16).mean(axis=0)
    out_np = np.asarray(out)[:2]
    np.testing.assert_allclose(out_np, ref_mean, atol=0.05)
    print("PASS compressed_psum")


def check_matmul_ag_overlap():
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 6))
    w = jax.random.normal(jax.random.PRNGKey(5), (6, 10))

    def body(xl, w):
        return matmul_ag_overlap(xl, w, "data")

    out = shard_map(body, mesh=mesh, in_specs=(P(None, "data", None), P()),
                        out_specs=P(None, None, None))(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               atol=1e-4, rtol=1e-4)
    print("PASS matmul_ag_overlap")


def check_moe_ep_matches_tp_dense():
    from repro.models.moe import init_moe, moe_apply_ep_a2a, \
        moe_apply_tp_dense
    mesh4 = make_mesh((4, 2), ("data", "model"))
    d, f, E = 16, 32, 4
    params = init_moe(jax.random.PRNGKey(6), d, f, E, jnp.float32)
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(7), (8, 4, d))
    y_dense, aux_d = moe_apply_tp_dense(params, x, top_k=2,
                                        capacity_factor=8.0)
    with mesh4:
        y_ep, aux_e = moe_apply_ep_a2a(
            params, x, top_k=2, capacity_factor=8.0, mesh=mesh4,
            dp_spec=P("data", None, None))
    # with generous capacity both drop nothing BUT dispatch order differs
    # between the global (dense) and per-shard (EP) capacity pools — compare
    # where both routed (no drops at cf=8 with T>=E*cap... assert close)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_dense),
                               atol=1e-4, rtol=1e-3)
    print("PASS moe_ep_matches_tp_dense")


def check_sharded_train_step():
    """One sharded train step on a 4x2 mesh == unsharded reference."""
    from repro.config import resolve
    from repro.configs import get_reduced
    from repro.models.model import LM
    from repro.models.runtime import Runtime
    from repro.train.optimizer import init_opt_state
    from repro.train.train_loop import TrainConfig, make_train_step
    from repro.distributed.sharding import tree_pspecs

    cfg = get_reduced("llama3_2_1b", dtype="float32", vocab_size=512,
                      num_layers=2, num_heads=4, num_kv_heads=2)
    rcfg = resolve(cfg, tp=2)
    m_ref = LM(rcfg, Runtime(attn_impl="naive", remat=False))
    params = m_ref.init(jax.random.PRNGKey(8))
    opt = init_opt_state(params)
    from repro.data.pipeline import SyntheticLMTask
    batch = {k: jnp.asarray(v) for k, v in
             SyntheticLMTask(512, 32).batch(0, 0, 0, 8).items()}
    _, _, met_ref = make_train_step(m_ref, None, TrainConfig())(
        params, opt, batch)

    m_sh = LM(rcfg, Runtime(attn_impl="naive", remat=False, mesh=mesh))
    pspecs = tree_pspecs(m_sh.param_specs(), mesh)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    params_sh = jax.tree.map(jax.device_put, params, pshard)
    step = jax.jit(make_train_step(m_sh, mesh, TrainConfig()))
    with mesh:
        _, _, met_sh = step(params_sh, opt, batch)
    np.testing.assert_allclose(float(met_sh["loss"]), float(met_ref["loss"]),
                               atol=1e-4, rtol=1e-4)
    print("PASS sharded_train_step")


def check_checkpoint_reshard():
    """Save under one sharding, restore under another mesh layout."""
    import tempfile
    from repro.checkpoint.checkpoint import Checkpointer
    x = jax.random.normal(jax.random.PRNGKey(9), (8, 16))
    xs = jax.device_put(x, NamedSharding(mesh, P("data", "model")))
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(1, {"x": xs})
        ck.wait()
        mesh2 = make_mesh((2, 4), ("data", "model"))
        tgt = {"x": NamedSharding(mesh2, P("model", None))}
        restored = ck.restore(1, {"x": x}, shardings=tgt)
        np.testing.assert_allclose(np.asarray(restored["x"]), np.asarray(x))
        assert restored["x"].sharding.spec == P("model", None)
    print("PASS checkpoint_reshard")


def check_elastic_remesh_training():
    """Full elastic-scaling path: train on a 2x2x2 'multi-pod' mesh,
    checkpoint, kill a pod, restore onto the surviving 2x2 mesh with
    resharded state + data-pipeline failover, keep training."""
    import tempfile
    from repro.config import resolve
    from repro.configs import get_reduced
    from repro.checkpoint.checkpoint import Checkpointer
    from repro.data.pipeline import DataPipeline, ShardPlan, SyntheticLMTask
    from repro.distributed.fault import plan_remesh
    from repro.distributed.sharding import tree_pspecs
    from repro.models.model import LM
    from repro.models.runtime import Runtime
    from repro.train.optimizer import init_opt_state
    from repro.train.train_loop import TrainConfig, make_train_step

    big = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_reduced("llama3_2_1b", dtype="float32", vocab_size=512,
                      num_layers=2, num_heads=4, num_kv_heads=2)
    rcfg = resolve(cfg, tp=2)

    def sharded(params, mesh):
        ps = tree_pspecs(LM(rcfg, Runtime(mesh=mesh)).param_specs(), mesh)
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), ps,
                          is_leaf=lambda x: isinstance(x, P))
        return jax.tree.map(jax.device_put, params, sh)

    m_big = LM(rcfg, Runtime(attn_impl="naive", remat=False, mesh=big))
    params = sharded(m_big.init(jax.random.PRNGKey(0)), big)
    opt = init_opt_state(params)
    task = SyntheticLMTask(512, 32)
    plan = ShardPlan(n_shards=4, n_hosts=2)
    pipe = DataPipeline(task, plan, host=0, batch_per_shard=4)
    step_big = jax.jit(make_train_step(m_big, big, TrainConfig()))
    with big:
        for _ in range(2):
            batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
            params, opt, met = step_big(params, opt, batch)
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(2, {"params": params, "opt": opt})
        ck.wait()
        # pod failure: 4 chips survive -> remesh plan
        rp = plan_remesh(4, old_dp=4)
        assert rp is not None and rp.chips == 4
        small = make_mesh((2, 2), ("data", "model"))
        m_small = LM(rcfg, Runtime(attn_impl="naive", remat=False,
                                   mesh=small))
        ps = tree_pspecs(m_small.param_specs(), small)
        sh = {"params": jax.tree.map(
            lambda s: NamedSharding(small, s), ps,
            is_leaf=lambda x: isinstance(x, P)), "opt": None}
        restored = ck.restore(2, {"params": params, "opt": opt},
                              shardings=None)
        params2 = sharded(restored["params"], small)
        pipe2 = pipe.with_failures([1])     # shard failover
        step_small = jax.jit(make_train_step(m_small, small, TrainConfig()))
        with small:
            batch = {k: jnp.asarray(v) for k, v in next(pipe2).items()}
            p3, o3, met3 = step_small(params2, restored["opt"], batch)
        assert np.isfinite(float(met3["loss"]))
    print("PASS elastic_remesh_training")


if __name__ == "__main__":
    check_ring_all_gather()
    check_ring_reduce_scatter()
    check_sp_decode_attention()
    check_compressed_psum()
    check_matmul_ag_overlap()
    check_moe_ep_matches_tp_dense()
    check_sharded_train_step()
    check_checkpoint_reshard()
    check_elastic_remesh_training()
    print("ALL_MULTIDEVICE_OK")
