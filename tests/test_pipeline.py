"""GPipe over the pod axis == sequential stage execution (subprocess)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import gpipe_forward
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("pod", "data"))
key = jax.random.PRNGKey(0)
n_stages, d = 2, 32
Ws = jax.random.normal(key, (n_stages, d, d), jnp.float32) / np.sqrt(d)
bs = jax.random.normal(jax.random.fold_in(key, 1), (n_stages, d), jnp.float32)

def stage(params, x):
    W, b = params
    return jnp.tanh(x @ W + b)

x = jax.random.normal(jax.random.fold_in(key, 2), (16, d), jnp.float32)

# Sequential reference.
ref = x
for i in range(n_stages):
    ref = stage((Ws[i], bs[i]), ref)

with mesh:
    got = jax.jit(lambda p, xx: gpipe_forward(
        mesh, stage, p, xx, n_micro=4))((Ws, bs), x)

np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                           rtol=1e-5, atol=1e-5)
print("PIPELINE_OK")
"""


@pytest.mark.slow
def test_gpipe_matches_sequential(tmp_path):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = tmp_path / "pipe.py"
    script.write_text(SCRIPT)
    out = subprocess.run([sys.executable, str(script), src],
                         capture_output=True, text=True, timeout=300)
    assert "PIPELINE_OK" in out.stdout, out.stdout + out.stderr[-3000:]
