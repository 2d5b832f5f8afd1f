"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Sections:
  1. kernel tables mirroring the paper (copy / scan / mapreduce / matvec /
     arbitrary-operator suite);
  2. roofline analysis over the multi-pod dry-run artifacts (§Roofline);
  3. a small *measured* end-to-end train-step microbench on the reduced
     config (CPU wall time -- the only honest wall-clock in this container).
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np


def train_microbench():
    print("\n== Train-step microbench (reduced config, host CPU) ==")
    from repro.configs import base as C
    from repro.training import optimizer as OPT
    from repro.training import train_step as TS
    from repro.training.data import DataConfig, SyntheticDataset
    cfg = C.get_config("minitron-4b", smoke=True)
    tc = TS.TrainConfig(optimizer=OPT.OptimizerConfig(), remat="none")
    data = SyntheticDataset(DataConfig(seq_len=64, global_batch=8,
                                       vocab_size=cfg.vocab_size), cfg)
    state = TS.init_state(jax.random.PRNGKey(0), cfg, tc)
    step = jax.jit(TS.make_train_step(cfg, None, tc), donate_argnums=(0,))
    state, m = step(state, data.batch(0))      # compile
    jax.block_until_ready(m["loss"])
    t0 = time.time()
    n = 5
    for s in range(1, n + 1):
        state, m = step(state, data.batch(s))
    jax.block_until_ready(m["loss"])
    dt = (time.time() - t0) / n
    toks = 8 * 64
    print(f"reduced minitron-4b: {dt*1e3:.1f} ms/step, "
          f"{toks/dt:.0f} tok/s (host CPU), loss={float(m['loss']):.3f}")


def main():
    print("=" * 72)
    print("KernelForge-TPU benchmark suite")
    print("=" * 72)
    from benchmarks import bench_kernels
    bench_kernels.main()

    print("\n" + "=" * 72)
    from benchmarks import roofline
    results_dir = os.path.join(os.path.dirname(__file__), "..",
                               "results", "dryrun")
    if os.path.isdir(results_dir) and os.listdir(results_dir):
        roofline.main(results_dir)
    else:
        print("(no dry-run artifacts under results/dryrun; run "
              "PYTHONPATH=src python -m repro.launch.dryrun first)")

    train_microbench()
    print("\nbenchmarks complete")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
