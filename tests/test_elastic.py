"""Elastic scaling: a checkpoint written under one mesh restores onto a
different mesh (the fleet-downsize path). The subprocess owns its own
device count (8 fake devices) so the main test process stays 1-device."""
import subprocess
import sys

import pytest

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.transformer import init_params, param_shapes
from repro.train import sharding as shd
from repro.train.checkpoint import CheckpointManager

cfg = get_config("qwen3-8b", smoke=True)
mesh_a = make_mesh((4, 2), ("data", "model"))
mesh_b = make_mesh((2, 4), ("data", "model"))
shapes = param_shapes(cfg)
shard_a = shd.param_shardings(cfg, mesh_a, shapes)
shard_b = shd.param_shardings(cfg, mesh_b, shapes)

with mesh_a:
    params = jax.jit(lambda k: init_params(cfg, k),
                     out_shardings=shard_a)(jax.random.PRNGKey(0))
with tempfile.TemporaryDirectory() as d:
    mgr = CheckpointManager(d)
    mgr.save(5, params, meta={"data_step": 5})
    like = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), params)
    with mesh_b:
        restored, meta = mgr.restore(like, shardings=shard_b)
    assert meta["data_step"] == 5
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # restored arrays actually live on mesh_b's sharding
    leaf = restored["blocks"]["wq"]
    assert leaf.sharding.mesh.shape["data"] == 2
print("elastic-ok")
"""


@pytest.mark.slow
def test_cross_mesh_restore():
    try:
        out = subprocess.run(
            [sys.executable, "-c", CODE], capture_output=True,
            text=True, timeout=300,
            env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    except subprocess.TimeoutExpired:
        # 8 fake devices + smoke-model jit can exceed the budget on slow
        # shared hosts; that is a capacity limit, not a restore bug.
        pytest.skip("cross-mesh smoke compile exceeded 300s on this host")
    assert "elastic-ok" in out.stdout, out.stderr[-2000:]
