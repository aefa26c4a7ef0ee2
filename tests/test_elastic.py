"""Elastic restart: a checkpoint written under one mesh restores onto a
DIFFERENT mesh shape (node-failure / re-scaling story). Runs in a subprocess
with forced host devices (main pytest process stays single-device)."""
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, tempfile; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P

from repro import checkpoint as ckpt_lib
from repro.configs import get_config, reduced, ShapeConfig
from repro.distributed import sharding as shd
from repro.models import get_model

cfg = reduced(get_config("qwen2-1.5b"), layers=2, d_model=64, vocab=128)
mod = get_model(cfg)
params = mod.init(jax.random.PRNGKey(0), cfg)

AUTO = (jax.sharding.AxisType.Auto,) * 2
mesh_a = jax.make_mesh((2, 4), ("data", "model"), axis_types=AUTO)
mesh_b = jax.make_mesh((4, 2), ("data", "model"), axis_types=AUTO)

# place params on mesh A, checkpoint, restore onto mesh B
specs_a = shd.param_specs(cfg, params, mesh_a)
sh_a = shd.tree_shardings(mesh_a, specs_a)
params_a = jax.tree_util.tree_map(
    lambda x, s: jax.device_put(x, s) if s is not None else x, params, sh_a)

with tempfile.TemporaryDirectory() as td:
    ckpt_lib.save(td, 1, {"params": params_a})
    specs_b = shd.param_specs(cfg, params, mesh_b)
    sh_b = shd.tree_shardings(mesh_b, specs_b)
    tree, meta = ckpt_lib.restore(td, shardings={"params": sh_b})

# values identical, new sharding applied
flat_old = jax.tree_util.tree_leaves(params)
flat_new = jax.tree_util.tree_leaves(tree["params"])
for a, b in zip(flat_old, flat_new):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0)

# forward pass works under the new mesh
with mesh_b:
    logits, _ = mod.forward(
        jax.tree_util.tree_map(jnp.asarray, tree["params"]),
        {"tokens": jnp.zeros((4, 8), jnp.int32)}, cfg,
        policy=__import__("repro.core.precision", fromlist=["FLOAT"]).FLOAT,
        dtype=jnp.float32)
assert not bool(jnp.any(jnp.isnan(logits)))
print("ELASTIC_OK")
"""


def test_elastic_remesh_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, cwd=".", timeout=300)
    assert "ELASTIC_OK" in r.stdout, r.stdout + r.stderr
