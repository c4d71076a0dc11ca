"""Distribution machinery unit tests (host-scale: 1 device).

Mesh/sharding resolution, HLO collective parsing, roofline terms, precision
policies over parameter trees, and the structural byte model.  The 512-way
production meshes are exercised by launch/dryrun.py (separate process with
forced host device count) -- these tests cover the logic around it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.precision import PrecisionPolicy, QTensor, quantize_tree
from repro.distributed.hlo_analysis import parse_collectives, roofline_terms
from repro.distributed.sharding import activation_rules, logical_spec
from repro.distributed.structural import model_flops, param_count, structural_bytes
from repro.models.common import dense, logical_to_mesh, partition_spec
from repro.models.registry import SHAPES, get_arch


def _mesh2(names=("data", "model")):
    dev = np.asarray(jax.devices()[:1]).reshape(1, 1)
    return Mesh(dev, names)


def test_partition_spec_divisibility_fallback():
    mesh_dev = np.asarray(jax.devices() * 16)[:16].reshape(4, 4)
    mesh = Mesh(mesh_dev, ("data", "model"))
    table = logical_to_mesh(mesh)
    ok = partition_spec(dense(8, 16, logical=("fsdp", "tp")), table, mesh)
    assert ok == P("data", "model")
    # 60 experts over a 4-way axis: 60 % 4 == 0 -> sharded; 30 % 4 != 0 -> dropped
    assert partition_spec(dense(60, 8, logical=("tp", None)), table, mesh)[0] == "model"
    assert partition_spec(dense(30, 8, logical=("tp", None)), table, mesh)[0] is None


def test_activation_rules_context():
    assert logical_spec("batch", None) is None  # inactive -> no constraints
    with activation_rules(_mesh2()):
        spec = logical_spec("batch", None, "tp")
        assert spec == P(("data",), None, "model")
    with activation_rules(_mesh2(("pod", "model"))):
        spec = logical_spec("batch", None)
        assert spec == P(("pod",), None)


def test_parse_collectives_accounting():
    hlo = """
  %ag = bf16[32,1024]{1,0} all-gather(bf16[2,1024]{1,0} %p), replica_groups=[16,16]<=[256], dimensions={0}
  %ar = f32[128]{0} all-reduce(f32[128]{0} %x), replica_groups={{0,1,2,3}}, to_apply=%add
  %cp = f32[64]{0} collective-permute(f32[64]{0} %y), source_target_pairs={{0,1}}
  %done = f32[8] all-reduce-done(f32[8] %h)
"""
    stats = parse_collectives(hlo)
    assert stats.by_op["all-gather"]["count"] == 1
    # AG: full 32*1024*2 bytes * (15/16)
    assert stats.by_op["all-gather"]["wire_bytes"] == pytest.approx(32 * 1024 * 2 * 15 / 16)
    # AR: 2 * 128*4 * (3/4)
    assert stats.by_op["all-reduce"]["wire_bytes"] == pytest.approx(2 * 128 * 4 * 3 / 4)
    assert stats.by_op["collective-permute"]["wire_bytes"] == pytest.approx(64 * 4)
    assert "all-reduce-done" not in stats.by_op


def test_roofline_terms_dominance():
    t = roofline_terms(197e12, 819e9 * 2, 0.0)  # 1s compute, 2s memory
    assert t["dominant"] == "memory_s"
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(2.0)


def test_model_flops_moe_counts_active_only():
    dense_arch = get_arch("phi3-medium-14b")
    moe_arch = get_arch("qwen2-moe-a2.7b")
    shape = SHAPES["train_4k"]
    f_moe = model_flops(moe_arch, shape)
    n_total = param_count(moe_arch)
    assert f_moe < 6.0 * n_total * shape.global_batch * shape.seq_len  # strictly less than dense-equivalent
    f_dense = model_flops(dense_arch, shape)
    assert f_dense == pytest.approx(6.0 * param_count(dense_arch) * shape.global_batch * shape.seq_len)


def test_structural_bytes_quant_shrinks_decode():
    arch = get_arch("gemma2-27b")
    shape = SHAPES["decode_32k"]
    base = structural_bytes(arch, shape)
    q8 = structural_bytes(arch, shape, quant_bits=8)
    q4 = structural_bytes(arch, shape, quant_bits=4)
    assert q8["params"] < base["params"] * 0.3
    assert q4["params"] < q8["params"] * 0.6
    assert q8["cache_read"] == base["cache_read"]


def test_precision_policy_tree_rules():
    params = {
        "blocks": {"pos0": {"mlp": {"w_up": jnp.ones((4, 8)), "w_down": jnp.ones((8, 4))}}},
        "final_norm": jnp.ones((4,)),
        "embed": jnp.ones((16, 4)),
    }
    policy = PrecisionPolicy(rules=(("w_(up|down)$", 8),))
    qt = quantize_tree(params, policy)
    assert isinstance(qt["blocks"]["pos0"]["mlp"]["w_up"], QTensor)
    assert isinstance(qt["embed"], jax.Array)  # unmatched -> untouched
    assert isinstance(qt["final_norm"], jax.Array)


def test_quantize_tree_stacked_layers():
    params = {"w_up": jnp.arange(2 * 4 * 6, dtype=jnp.float32).reshape(2, 4, 6)}
    qt = quantize_tree(params, PrecisionPolicy(rules=(("w_up", 8),)))
    assert qt["w_up"].q.shape == (2, 4, 6)
    assert qt["w_up"].scale.shape == (2, 6)


def test_elastic_mesh_roundtrip_with_checkpointer(tmp_path):
    """Save under one sharding, restore under another (1-device meshes with
    different axis names stand in for different pod counts)."""
    from repro.checkpoint.checkpointer import Checkpointer

    tree = {"w": jnp.arange(8.0).reshape(2, 4)}
    ck = Checkpointer(tmp_path)
    ck.save(1, tree, blocking=True)
    mesh = _mesh2(("data", "model"))
    sharding = {"w": jax.sharding.NamedSharding(mesh, P("data", "model"))}
    restored, _ = ck.restore({"w": jnp.zeros((2, 4))}, shardings=sharding)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(8.0).reshape(2, 4))
    assert restored["w"].sharding.spec == P("data", "model")


@pytest.mark.parametrize("from_env", [True, False], ids=["env_dir", "checkout_dir"])
def test_enable_compilation_cache_populates(tmp_path, monkeypatch, from_env):
    """The persistent jit cache goes where ``JAX_COMPILATION_CACHE_DIR``
    says, else to ``.jax_cache/`` at the checkout root; compiles land there."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.distributed import compat

    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = tmp_path
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        # keep the test's entries out of the real checkout
        monkeypatch.setattr(compat, "_CHECKOUT_ROOT", tmp_path)
        want = tmp_path / ".jax_cache"
    try:
        got = compat.enable_compilation_cache()
        assert got == want
        assert jax.config.jax_compilation_cache_dir == str(want)
        fn = jax.jit(lambda x: x * 3 + 7)
        np.testing.assert_allclose(np.asarray(fn(jnp.arange(64.0))), np.arange(64.0) * 3 + 7)
        assert list(want.iterdir()), "no cache entries written"
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
        cc.reset_cache()


def test_ring_allgather_matmul_matches_dense():
    """Ring-overlap matmul == plain matmul (single-device ring degenerates
    to the direct product; the slicing/permute index algebra is what's
    under test and is ring-size-generic)."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.distributed.overlap import ring_allgather_matmul_shardmap

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("model",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 4), jnp.float32)
    fn = jax.jit(ring_allgather_matmul_shardmap(mesh, "model"))
    np.testing.assert_allclose(np.asarray(fn(x, w)), np.asarray(x @ w), rtol=1e-5)
