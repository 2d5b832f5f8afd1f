"""Distributed flash-decoding: sharded-vs-local decode parity.

(The SOFTMAX_MERGE operator-fold equivalence assertion that used to live
here moved to tests/test_sharded.py, where it is exercised both in numpy
form and through the real 8-device collective behind
``mapreduce(SOFTMAX_MERGE, layout=Sharded(...))``.)
"""
import os
import subprocess
import sys

import pytest

SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import base as C
from repro.distributed import sharding as SH
from repro.models import lm
from repro.training import train_step as TS
from repro.launch.mesh import make_mesh

from repro.models import layers as L

# gemma3: kv heads (2) do not divide model (4) -> GQA flash-decoding path.
# dsv3:   MLA compressed cache -> latent-space flash-decoding path.
for arch in ["gemma3-4b", "deepseek-v3-671b"]:
    cfg = C.get_config(arch, smoke=True)
    cfg = dataclasses.replace(cfg, dtype="float32", capacity_factor=16.0)
    mesh = make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(0)
    B, S = 2, 16
    cache_len = 32  # divisible by model axis
    params = lm.init_params(key, cfg)
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size, jnp.int32)

    # Reference: unsharded path (no mesh -> plain decode_attention).
    ref_logits, ref_caches = lm.prefill(params, cfg, toks[:, :S-1],
                                        cache_len=cache_len)
    ref_step, _ = lm.decode_step(params, cfg, ref_caches, toks[:, S-1:S],
                                 jnp.asarray(S-1, jnp.int32))

    with mesh:
        rules = SH.make_rules(cfg, mesh)
        def prefill_f32(p, batch):
            with L.sharding_rules(rules):
                return lm.prefill(p, cfg, batch["tokens"],
                                  cache_len=cache_len)
        def decode_f32(p, c, t, pos):
            with L.sharding_rules(rules):
                return lm.decode_step(p, cfg, c, t, pos)
        logits, caches = jax.jit(prefill_f32)(params,
                                              {"tokens": toks[:, :S-1]})
        step_logits, _ = jax.jit(decode_f32)(params, caches, toks[:, S-1:S],
                                             jnp.asarray(S-1, jnp.int32))

    np.testing.assert_allclose(np.asarray(step_logits), np.asarray(ref_step),
                               rtol=2e-3, atol=2e-3, err_msg=arch)
    print(f"{arch}: sharded == local")
print("FLASH_DECODE_OK")
"""


@pytest.mark.slow
def test_sharded_decode_matches_local(tmp_path):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = tmp_path / "sharded.py"
    script.write_text(SHARDED_SCRIPT)
    out = subprocess.run([sys.executable, str(script), src],
                         capture_output=True, text=True, timeout=560)
    assert "FLASH_DECODE_OK" in out.stdout, out.stdout + out.stderr[-3000:]
