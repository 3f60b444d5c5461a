"""Multi-device SPMD correctness: run small models on 8 fake host devices
in a SUBPROCESS (the test process itself must keep the default single
device; jax locks device count at first init)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro import configs
from repro.configs.base import ShapeConfig
from repro.launch.steps import build_program
from repro.models import registry
from repro.train import optimizer as opt_mod
from repro.train.optimizer import OptConfig
from repro.distributed import sharding as shd

out = {}
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)

# --- dense arch: sharded loss == single-device loss ---
cfg = configs.get_reduced("llama3-8b").replace(
    num_layers=2, d_model=64, num_heads=8, num_kv_heads=4, d_ff=128,
    vocab_size=256, head_dim=16, dtype="float32", param_dtype="float32")
model = registry.build(cfg)
params = model.init(0)
rng = np.random.RandomState(0)
batch = {"tokens": jnp.asarray(rng.randint(0, 256, (4, 64)), jnp.int32),
         "labels": jnp.asarray(rng.randint(0, 256, (4, 64)), jnp.int32)}
loss_1dev = float(jax.jit(model.loss)(params, batch))

rules = registry.make_rules(cfg, mesh, "train")
with shd.use_mesh(mesh, rules):
    loss_sharded = float(jax.jit(model.loss)(params, batch))
out["dense_loss_match"] = abs(loss_1dev - loss_sharded) < 1e-4

# --- train step compiles + runs with explicit shardings ---
shape = ShapeConfig("t", 64, 4, "train")
jitted, args, rules = build_program(cfg, shape, mesh)
p = model.init(0)
st = opt_mod.init_state(OptConfig(), p)
p2, st2, metrics = jitted(p, st, batch)
out["train_step_finite"] = bool(np.isfinite(float(metrics["loss"])))

# --- MoE with real expert parallelism: matches single-device ---
mcfg = configs.get_reduced("phi3.5-moe-42b-a6.6b").replace(
    dtype="float32", param_dtype="float32")
import dataclasses
mcfg = mcfg.replace(moe=dataclasses.replace(mcfg.moe, capacity_factor=8.0))
mmodel = registry.build(mcfg)
mparams = mmodel.init(0)
mb = {"tokens": jnp.asarray(rng.randint(0, 256, (4, 32)), jnp.int32),
      "labels": jnp.asarray(rng.randint(0, 256, (4, 32)), jnp.int32)}
l1 = float(jax.jit(mmodel.loss)(mparams, mb))
mrules = registry.make_rules(mcfg, mesh, "train")
with shd.use_mesh(mesh, mrules):
    l2 = float(jax.jit(mmodel.loss)(mparams, mb))
out["moe_ep_loss_match"] = abs(l1 - l2) < 1e-3

# --- decode with sequence-sharded KV cache == unsharded decode ---
dcfg = cfg
dmodel = registry.build(dcfg)
dparams = dmodel.init(0)
toks = jnp.asarray(rng.randint(0, 256, (4, 32)), jnp.int32)
lp, cache = dmodel.prefill(dparams, {"tokens": toks}, cache_len=64)
ld_ref, _ = dmodel.decode_step(dparams, cache,
                               {"tokens": toks[:, :1]})
drules = registry.make_rules(dcfg, mesh, "decode")
with shd.use_mesh(mesh, drules):
    lp2, cache2 = jax.jit(
        lambda p, b: dmodel.prefill(p, b, cache_len=64))(dparams,
                                                         {"tokens": toks})
    ld_sh, _ = jax.jit(dmodel.decode_step)(dparams, cache2,
                                           {"tokens": toks[:, :1]})
out["decode_seqshard_match"] = bool(
    np.max(np.abs(np.asarray(ld_ref) - np.asarray(ld_sh))) < 1e-3)

# --- disaggregated embedding lookup across a 4-shard MN pool ---
from repro.core import sharding as core_shd
tables = jnp.asarray(rng.randn(8, 64, 16), jnp.float32)
idx = jnp.asarray(rng.randint(0, 64, (4, 8, 5)), jnp.int32)
from repro.models.dlrm import embedding_bag_ref
want = embedding_bag_ref(tables, idx)
with shd.use_mesh(mesh, None):
    got = core_shd.disagg_embedding_lookup(tables, idx, mesh=mesh)
out["disagg_lookup_match"] = bool(
    np.max(np.abs(np.asarray(got) - np.asarray(want))) < 1e-4)

# --- elastic: reshard onto a shrunken mesh after 'failures' ---
from repro.distributed import elastic
small = elastic.healthy_mesh({"model": 4}, failed_fraction=0.4)
out["elastic_mesh_devices"] = int(small.devices.size)
p_resh = elastic.reshard_tree(params, model.param_specs(), small, rules)
out["elastic_reshard_ok"] = bool(np.isfinite(
    float(jax.jit(model.loss)(p_resh, batch))))

print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def spmd_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_dense_sharded_loss_matches(spmd_results):
    assert spmd_results["dense_loss_match"]


def test_train_step_runs_sharded(spmd_results):
    assert spmd_results["train_step_finite"]


def test_moe_expert_parallel_matches(spmd_results):
    assert spmd_results["moe_ep_loss_match"]


def test_decode_sequence_sharded_cache_matches(spmd_results):
    assert spmd_results["decode_seqshard_match"]


def test_disaggregated_embedding_lookup(spmd_results):
    assert spmd_results["disagg_lookup_match"]


def test_elastic_reshard(spmd_results):
    assert spmd_results["elastic_mesh_devices"] == 4  # 8*0.6 -> 4 (4x1)
    assert spmd_results["elastic_reshard_ok"]
