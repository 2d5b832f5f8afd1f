"""End-to-end serving driver: continuous batching with on-device decode.

    PYTHONPATH=src python examples/serve_decode.py [--arch gemma2-27b]

Uses the reduced (smoke) config of the chosen architecture so it runs on any
host; the same Engine drives the full configs on real hardware (the mesh and
shardings come from the same builders the dry-run compiles).

Two demos: a closed batch (``generate`` -- everything admitted at step 0,
one device-loop dispatch decodes the whole batch to completion) and an
open-loop Poisson trace (``serve`` -- more requests than slots, admitted as
earlier requests hit their budget and free their slot).
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import base as C
from repro.models import lm
from repro.serving.engine import Engine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-27b",
                    choices=C.list_archs())
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()

    cfg = C.get_config(args.arch, smoke=True)
    print(f"[serve] arch={args.arch} (reduced config: {cfg.n_layers}L "
          f"d={cfg.d_model})")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, None, params, cache_len=256, batch_size=args.batch,
                 temperature=0.0)

    rng = np.random.default_rng(0)
    reqs = [Request(prompt=list(rng.integers(1, cfg.vocab_size, 12)),
                    max_new_tokens=args.max_new)
            for _ in range(args.batch)]
    outs = eng.generate(reqs)           # includes compile
    t0 = time.time()
    outs = eng.generate(reqs)           # steady-state
    dt = time.time() - t0
    for i, o in enumerate(outs):
        print(f"  req{i}: {o[:12]}{'...' if len(o) > 12 else ''}")
    s = eng.last_stats
    print(f"[serve] decode {s['decode_tok_per_s']:.1f} tok/s (host CPU), "
          f"{s['loop_dispatches']} device-loop dispatch(es), wall {dt:.2f}s")

    # Open-loop traffic: 3x more requests than slots arriving over time;
    # the scheduler recycles slots as requests finish.
    trace = []
    step = 0.0
    for i in range(3 * args.batch):
        step += rng.exponential(2.0)
        trace.append((int(step), Request(
            prompt=list(rng.integers(1, cfg.vocab_size, 8)),
            max_new_tokens=int(rng.integers(4, args.max_new + 1)), seed=i)))
    recs = eng.serve(trace)
    s = eng.last_stats
    lat = [r.finish_step - r.submit_step for r in recs]
    print(f"[serve] open loop: {len(recs)} requests through "
          f"{args.batch} slots, {s['decode_tok_per_s']:.1f} tok/s, "
          f"latency p50 {int(np.percentile(lat, 50))} steps / "
          f"max {max(lat)} steps, {s['loop_dispatches']} dispatches")


if __name__ == "__main__":
    main()
