"""Pipeline parallelism == sequential stage application.

Runs in a SUBPROCESS with forced host devices so the main pytest process
keeps the mandated single-device view."""
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import pipeline_apply

mesh = jax.make_mesh((4,), ("stage",),
                     axis_types=(jax.sharding.AxisType.Auto,))
key = jax.random.PRNGKey(0)
S, M, B, D = 4, 6, 2, 8
ws = jax.random.normal(key, (S, D, D)) * 0.3
bs = jax.random.normal(jax.random.PRNGKey(1), (S, D)) * 0.1
params = {"w": ws, "b": bs}
x = jax.random.normal(jax.random.PRNGKey(2), (M, B, D))

def stage_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])

out = pipeline_apply(stage_fn, params, x, mesh)

ref = x
for s in range(S):
    ref = jnp.tanh(ref @ ws[s] + bs[s])
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

# differentiability: grad through the pipeline matches sequential grad
def loss_pp(ws_):
    o = pipeline_apply(stage_fn, {"w": ws_, "b": bs}, x, mesh)
    return jnp.sum(o ** 2)

def loss_seq(ws_):
    h = x
    for s in range(S):
        h = jnp.tanh(h @ ws_[s] + bs[s])
    return jnp.sum(h ** 2)

g_pp = jax.grad(loss_pp)(ws)
g_seq = jax.grad(loss_seq)(ws)
np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_seq),
                           atol=1e-4, rtol=1e-4)
print("PIPELINE_OK")
"""


def test_pipeline_matches_sequential_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, cwd=".", timeout=300)
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr
