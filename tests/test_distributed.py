"""First coverage for distributed/sharding.py + launch/mesh.py.

These modules predate any test: `spec_for`'s divisibility
degrade-to-replication, rule priority order, and the fsdp toggle were
only exercised implicitly by the launch dry-run. Production-shape
checks use `jax.sharding.AbstractMesh` — a 16x16 (or 2x16x16) mesh
needs no devices to answer axis-bookkeeping questions — while the
paged-pool helpers (kv_shard_count, shard_paged_pool, replicate) run on
the conftest-forced simulated host devices.
"""
from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import sharding
from repro.launch import mesh as mesh_lib


def _prod_mesh(multi_pod=False):
    if multi_pod:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


# -------------------------------------------------------- spec_for ---------
def test_spec_for_shards_divisible_dims():
    rules = sharding.ShardingRules()
    # (vocab=32000, embed=4096): vocab -> model(16), embed -> data(16)
    spec = rules.spec_for((32000, 4096), ("vocab", "embed"), _prod_mesh())
    assert spec == P("model", "data")


def test_spec_for_degrades_to_replication_on_indivisible():
    rules = sharding.ShardingRules()
    # 8 kv-heads don't divide a 16-way model axis -> replicated, while the
    # divisible head_dim axis stays unsharded too (no rule for None)
    spec = rules.spec_for((8, 128), ("heads", None), _prod_mesh())
    assert spec == P(None, None)
    # same logical axis, divisible shape -> sharded
    assert rules.spec_for((32, 128), ("heads", None),
                          _prod_mesh()) == P("model", None)


def test_spec_for_never_reuses_a_mesh_axis():
    rules = sharding.ShardingRules()
    # two dims both preferring "model": first wins, second degrades
    spec = rules.spec_for((32, 64), ("heads", "mlp"), _prod_mesh())
    assert spec == P("model", None)


def test_spec_for_skips_axes_absent_from_mesh():
    rules = sharding.ShardingRules()
    model_only = AbstractMesh((16,), ("model",))
    # "embed" prefers "data", which this mesh lacks -> replicated
    spec = rules.spec_for((4096, 32000), ("embed", "vocab"), model_only)
    assert spec == P(None, "model")


def test_fsdp_toggle_drops_data_axis():
    on = sharding.ShardingRules(fsdp=True)
    off = sharding.ShardingRules(fsdp=False)
    assert on.mesh_axes_for("embed") == ("data",)
    assert off.mesh_axes_for("embed") == ()
    assert on.spec_for((4096,), ("embed",), _prod_mesh()) == P("data")
    assert off.spec_for((4096,), ("embed",), _prod_mesh()) == P(None)
    # fsdp never touches tensor-parallel rules
    assert off.mesh_axes_for("heads") == ("model",)


def test_unknown_logical_axis_replicates():
    rules = sharding.ShardingRules()
    assert rules.mesh_axes_for("no-such-axis") == ()
    assert rules.mesh_axes_for(None) == ()
    assert rules.spec_for((64,), (None,), _prod_mesh()) == P(None)


# ------------------------------------------------- paged-pool helpers ------
def test_paged_pool_pspec_shape():
    assert sharding.paged_pool_pspec() == P(None, None, "model")


def test_kv_shard_count_validates():
    cfg = ModelConfig(name="t", family="decoder", num_layers=1, d_model=64,
                      num_heads=8, num_kv_heads=8, d_ff=64, vocab_size=64,
                      head_dim=8)
    mesh = AbstractMesh((1, 4), ("data", "model"))
    assert sharding.kv_shard_count(cfg, mesh) == 4
    with pytest.raises(ValueError, match="no 'model' axis"):
        sharding.kv_shard_count(cfg, AbstractMesh((4,), ("data",)))
    import dataclasses as dc
    gqa = dc.replace(cfg, num_kv_heads=3, num_heads=6)
    with pytest.raises(ValueError, match="cannot shard"):
        sharding.kv_shard_count(gqa, mesh)
    # GQA split stays legal when the group structure divides
    assert sharding.kv_shard_count(
        dc.replace(cfg, num_kv_heads=4, num_heads=8), mesh) == 4


def test_shard_paged_pool_splits_head_axis(sim_mesh_devices):
    mesh = mesh_lib.make_sim_mesh(2, sim_mesh_devices)
    leaf = np.arange(2 * 4 * 4 * 8 * 3, dtype=np.float32).reshape(
        2, 4, 4, 8, 3)
    tree = {"k": leaf, "v": leaf + 1.0}
    out = sharding.shard_paged_pool(tree, mesh)
    for name, arr in out.items():
        np.testing.assert_array_equal(np.asarray(arr), tree[name])
        shards = arr.addressable_shards
        assert len(shards) == 2
        # head axis (2) is halved per device, all other dims intact
        assert all(s.data.shape == (2, 4, 2, 8, 3) for s in shards)


def test_replicate_keeps_full_copies(sim_mesh_devices):
    mesh = mesh_lib.make_sim_mesh(2, sim_mesh_devices)
    arr = np.arange(12, dtype=np.int32).reshape(3, 4)
    out = sharding.replicate({"w": arr}, mesh)["w"]
    assert all(s.data.shape == arr.shape
               for s in out.addressable_shards)
    np.testing.assert_array_equal(np.asarray(out), arr)


# ----------------------------------------------------- launch/mesh ---------
def test_batch_axes_and_axis_size_production_shapes():
    single = _prod_mesh()
    multi = _prod_mesh(multi_pod=True)
    assert mesh_lib.batch_axes(single) == ("data",)
    assert mesh_lib.batch_axes(multi) == ("pod", "data")
    assert mesh_lib.axis_size(single, "data") == 16
    assert mesh_lib.axis_size(single, "model") == 16
    assert mesh_lib.axis_size(multi, "pod", "data") == 32
    # absent axes contribute a factor of 1, not an error
    assert mesh_lib.axis_size(single, "pod", "data") == 16
    assert mesh_lib.axis_size(single) == 1


def test_host_mesh_axes(sim_mesh_devices):
    mesh = mesh_lib.make_host_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh_lib.batch_axes(mesh) == ("data",)
    assert mesh_lib.axis_size(mesh, "data") == 1
    assert (mesh_lib.axis_size(mesh, "data", "model")
            == len(jax.devices()))


def test_make_sim_mesh(sim_mesh_devices):
    mesh = mesh_lib.make_sim_mesh(2, sim_mesh_devices)
    assert mesh.axis_names == ("data", "model")
    assert dict(mesh.shape) == {"data": 1, "model": 2}
    assert mesh_lib.batch_axes(mesh) == ("data",)
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        mesh_lib.make_sim_mesh(len(sim_mesh_devices) + 1, sim_mesh_devices)
