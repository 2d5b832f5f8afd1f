"""Checkpoint tests: roundtrip, atomicity, async, elastic restore."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_trees_close
from repro.configs import base as C
from repro.training import checkpoint as CKPT
from repro.training import optimizer as OPT
from repro.training import train_step as TS


def _state(rng):
    cfg = C.get_config("gemma2-27b", smoke=True)
    tc = TS.TrainConfig(optimizer=OPT.OptimizerConfig())
    return TS.init_state(rng, cfg, tc)


def test_roundtrip(tmp_path, rng):
    state = _state(rng)
    CKPT.save(str(tmp_path), 7, state)
    shape = jax.eval_shape(lambda: state)
    restored = CKPT.restore(str(tmp_path), 7, shape)
    assert_trees_close(restored, state, rtol=0, atol=0)


def test_latest_and_cleanup(tmp_path, rng):
    state = _state(rng)
    for s in [1, 2, 3, 4]:
        CKPT.save(str(tmp_path), s, state)
    assert CKPT.latest_step(str(tmp_path)) == 4
    CKPT.cleanup(str(tmp_path), keep=2)
    assert sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path)) == [3, 4]


def test_atomicity_tmp_never_visible(tmp_path, rng):
    state = _state(rng)
    CKPT.save(str(tmp_path), 1, state)
    # A leftover tmp dir (simulated crash) is ignored by latest_step.
    os.makedirs(tmp_path / "step_9.tmp")
    assert CKPT.latest_step(str(tmp_path)) == 1


def test_async_checkpointer(tmp_path, rng):
    state = _state(rng)
    ck = CKPT.AsyncCheckpointer(str(tmp_path), keep=2)
    ck.save(1, state)
    ck.save(2, state)   # waits for the first
    ck.wait()
    assert CKPT.latest_step(str(tmp_path)) == 2


def test_manifest_schema(tmp_path, rng):
    state = _state(rng)
    path = CKPT.save(str(tmp_path), 3, state)
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["step"] == 3
    n_leaves = len(jax.tree.leaves(state))
    assert len(manifest["leaves"]) == n_leaves
    for meta in manifest["leaves"].values():
        assert os.path.exists(os.path.join(path, meta["file"]))


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from repro.configs import base as C
from repro.distributed import sharding as SH
from repro.training import checkpoint as CKPT, optimizer as OPT, train_step as TS
from repro.launch.mesh import make_mesh

cfg = C.get_config("gemma2-27b", smoke=True)
tc = TS.TrainConfig(optimizer=OPT.OptimizerConfig())
state = TS.init_state(jax.random.PRNGKey(0), cfg, tc)
ckpt_dir = sys.argv[2]

# Save from a (4 data x 2 model) mesh...
mesh_a = make_mesh((4, 2), ("data", "model"))
CKPT.save(ckpt_dir, 5, state)

# ...restore onto a (2 data x 4 model) mesh: elastic resharding on load.
mesh_b = make_mesh((2, 4), ("data", "model"))
shape = jax.eval_shape(lambda: state)
specs = TS.state_specs(shape, cfg, mesh_b)
shardings = SH.named(mesh_b, specs)
restored = CKPT.restore(ckpt_dir, 5, shape, shardings)
for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(a.sharding.device_set) >= 1
print("ELASTIC_OK")
"""


def test_elastic_restart_across_meshes(tmp_path):
    """Deliverable: checkpoint saved under one mesh restores onto another
    (different data/model split) with identical values -- elastic scaling."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = tmp_path / "elastic.py"
    script.write_text(ELASTIC_SCRIPT)
    out = subprocess.run(
        [sys.executable, str(script), src, str(tmp_path / "ck")],
        capture_output=True, text=True, timeout=300)
    assert "ELASTIC_OK" in out.stdout, out.stdout + out.stderr
