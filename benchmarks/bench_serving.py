"""Serving benchmark: continuous batching vs the padded-batch baseline.

Drives the continuous-batching engine with a synthetic **open-loop Poisson
arrival trace** (exponential inter-arrival gaps in decode-step units, seeded
=> reproducible) and reports:

* decode throughput (tokens/s, wall-clock) and device-loop dispatch count;
* per-request latency: submit -> finish in decode *steps* (deterministic,
  the CI-stable quantity) and modeled seconds (steps x measured s/step);
* the same request set through the legacy padded fixed-batch path, giving a
  **machine-independent throughput ratio** (continuous / padded on the same
  host, same model, same requests).

``--ci`` runs the small smoke configuration, writes ``BENCH_serving.json``
and hard-fails if the throughput ratio regresses more than 10% below the
committed baseline (``benchmarks/BENCH_serving_baseline.json``).  The ratio
-- not absolute tokens/s -- is gated so the check survives runner-hardware
changes: both paths run the same matmuls on the same machine, so the ratio
isolates exactly what continuous batching is supposed to buy (no per-token
host syncs, no padded-slot waste, slot recycling under load).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np


def poisson_trace(rng, n_requests, rate, vocab, max_plen, max_new):
    """Open-loop arrivals: (step, Request) with exp(rate) gaps, random
    prompts/budgets -- the load is generated regardless of server state."""
    from repro.serving.engine import Request

    arrivals, step = [], 0.0
    for i in range(n_requests):
        step += rng.exponential(1.0 / rate)
        plen = int(rng.integers(2, max_plen + 1))
        prompt = rng.integers(1, vocab, plen).tolist()
        arrivals.append((int(step), Request(
            prompt=prompt, max_new_tokens=int(rng.integers(2, max_new + 1)),
            seed=1000 + i)))
    return arrivals


def run_continuous(eng, arrivals):
    recs = eng.serve(arrivals)
    st = eng.last_stats
    lat_steps = np.asarray([r.finish_step - r.submit_step for r in recs],
                           np.float64)
    s_per_step = st["decode_s"] / max(st["decode_steps"], 1)
    return {
        "tok_per_s": st["decode_tok_per_s"],
        "total_tokens": st["total_tokens"],
        "decode_steps": st["decode_steps"],
        "loop_dispatches": st["loop_dispatches"],
        "admissions": st["admissions"],
        "decode_s": st["decode_s"],
        "latency_steps": {
            "p50": float(np.percentile(lat_steps, 50)),
            "p99": float(np.percentile(lat_steps, 99)),
            "max": float(lat_steps.max()),
        },
        # steps are the deterministic latency unit; seconds are modeled from
        # the measured step time so the numbers travel across hosts.
        "latency_s_modeled": {
            "p50": float(np.percentile(lat_steps, 50) * s_per_step),
            "p99": float(np.percentile(lat_steps, 99) * s_per_step),
        },
        "s_per_step": s_per_step,
    }


def run_padded(eng, arrivals):
    """Same requests through the legacy fixed-batch path, admitted in
    arrival order in full batches (its best case: no arrival gaps modeled,
    so the ratio under-states the continuous win under sparse traffic)."""
    reqs = [r for _, r in arrivals]
    toks = 0
    decode_s = 0.0
    for i in range(0, len(reqs), eng.batch_size):
        chunk = reqs[i:i + eng.batch_size]
        outs = eng.generate_padded(chunk)
        toks += sum(len(o) for o in outs)
        decode_s += eng.last_stats["decode_s"]
    return {"tok_per_s": toks / max(decode_s, 1e-9),
            "total_tokens": toks, "decode_s": decode_s}


def run_bench(*, arch, cache_len, batch_size, n_requests, rate, max_plen,
              max_new, seed, temperature, top_k):
    from repro.configs import base as C
    from repro.models import lm
    from repro.serving.engine import Engine, Request

    cfg = C.get_config(arch, smoke=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)
    arrivals = poisson_trace(rng, n_requests, rate, cfg.vocab_size,
                             max_plen, max_new)
    kw = dict(cache_len=cache_len, batch_size=batch_size,
              temperature=temperature, top_k=top_k)

    # Warm the measured engine's jit caches off the clock (jit caches live
    # on the engine's closures, so warming a different instance warms
    # nothing): a small trace that touches every prompt length plus both
    # loop variants (arrival-bounded and free-slot-bounded).
    cont_eng = Engine(cfg, None, params, **kw)
    cont_eng.serve(
        [(0, Request(prompt=list(range(1, p + 1)), max_new_tokens=2, seed=0))
         for p in range(2, max_plen + 1)] +
        [(1, Request(prompt=[1, 2], max_new_tokens=2, seed=0))])

    # Best-of-N on both paths: the gated quantity is their ratio, and taking
    # each side's best run strips scheduler-noise outliers that would flake
    # a 10% gate on a single sample.
    repeats = 3
    t0 = time.time()
    cont = max((run_continuous(cont_eng, arrivals) for _ in range(repeats)),
               key=lambda r: r["tok_per_s"])
    cont["wall_s"] = time.time() - t0

    pad_eng = Engine(cfg, None, params, **kw)
    pad_eng.generate_padded([Request(prompt=[1, 2], max_new_tokens=2,
                                     seed=0)])            # warm
    padded = max((run_padded(pad_eng, arrivals) for _ in range(repeats)),
                 key=lambda r: r["tok_per_s"])

    # Quantized-KV leg: same trace through the continuous engine with the
    # opt-in int8 KV cache.  The recorded quantity is again a same-host
    # ratio (quantized / dense continuous) -- on CPU smoke it mostly prices
    # the per-step quantize/dequantize overhead; on real accelerators it
    # shows the HBM-bytes win.
    qkv_eng = Engine(cfg, None, params, quantize_kv="int8", **kw)
    qkv_eng.serve(
        [(0, Request(prompt=list(range(1, p + 1)), max_new_tokens=2, seed=0))
         for p in range(2, max_plen + 1)] +
        [(1, Request(prompt=[1, 2], max_new_tokens=2, seed=0))])  # warm
    qkv = max((run_continuous(qkv_eng, arrivals) for _ in range(repeats)),
              key=lambda r: r["tok_per_s"])
    qkv["mode"] = "int8"
    qkv["ratio_vs_dense"] = qkv["tok_per_s"] / cont["tok_per_s"]

    # Speculative leg: the same trace decoded draft-and-verify.  The draft
    # here is the target itself (zero-cost stand-in with a perfect-ish
    # acceptance rate under greedy; sampled traces accept less), so the leg
    # prices the strategy machinery -- scan-of-(k+1)-substeps vs
    # one-token rounds -- and records the acceptance telemetry.  Tokens/s is
    # again reported as a same-host ratio vs dense continuous.
    from repro.serving.strategies import BeamSearch, Speculative

    spec_eng = Engine(cfg, None, params,
                      strategy=Speculative(cfg, params, k=3), **kw)
    spec_eng.serve(
        [(0, Request(prompt=list(range(1, p + 1)), max_new_tokens=2, seed=0))
         for p in range(2, max_plen + 1)] +
        [(1, Request(prompt=[1, 2], max_new_tokens=2, seed=0))])  # warm
    spec = max((run_continuous(spec_eng, arrivals) for _ in range(repeats)),
               key=lambda r: r["tok_per_s"])
    st = spec_eng.last_stats
    spec["k"] = 3
    spec["acceptance_rate"] = st["spec_acceptance_rate"]
    spec["rounds"] = st["spec_rounds"]
    spec["proposed"] = st["spec_proposed"]
    spec["accepted"] = st["spec_accepted"]
    spec["ratio_vs_dense"] = spec["tok_per_s"] / cont["tok_per_s"]

    # Beam leg: width-2 beams per slot (beam search is deterministic, so
    # its engine runs greedy regardless of the trace's sampling settings).
    beam_kw = dict(kw, temperature=0.0, top_k=0)
    beam_eng = Engine(cfg, None, params, strategy=BeamSearch(width=2),
                      **beam_kw)
    beam_eng.serve(
        [(0, Request(prompt=list(range(1, p + 1)), max_new_tokens=2, seed=0))
         for p in range(2, max_plen + 1)] +
        [(1, Request(prompt=[1, 2], max_new_tokens=2, seed=0))])  # warm
    beam = max((run_continuous(beam_eng, arrivals) for _ in range(repeats)),
               key=lambda r: r["tok_per_s"])
    beam["width"] = 2
    beam["ratio_vs_dense"] = beam["tok_per_s"] / cont["tok_per_s"]

    return {
        "config": {"arch": arch, "cache_len": cache_len,
                   "batch_size": batch_size, "n_requests": n_requests,
                   "poisson_rate": rate, "max_plen": max_plen,
                   "max_new": max_new, "seed": seed,
                   "temperature": temperature, "top_k": top_k,
                   "backend": jax.default_backend(),
                   "jax": jax.__version__},
        "continuous": cont,
        "padded": padded,
        "quantized_kv": qkv,
        "speculative": spec,
        "beam": beam,
        "ratio_vs_padded": cont["tok_per_s"] / padded["tok_per_s"],
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ci", action="store_true",
                    help="smoke sizes + regression gate vs the baseline")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--baseline", default=None,
                    help="baseline json; with --ci, fail if ratio_vs_padded "
                         "drops >10%% below its ratio")
    ap.add_argument("--arch", default="gemma2-27b")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="Poisson arrival rate (requests per decode step)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    n_requests = args.requests or (10 if args.ci else 32)
    result = run_bench(
        arch=args.arch, cache_len=64, batch_size=4, n_requests=n_requests,
        rate=args.rate, max_plen=6, max_new=10, seed=args.seed,
        temperature=0.8, top_k=5)

    c, p = result["continuous"], result["padded"]
    print(f"continuous: {c['tok_per_s']:8.1f} tok/s  "
          f"({c['total_tokens']} tokens, {c['decode_steps']} steps, "
          f"{c['loop_dispatches']} loop dispatches)")
    print(f"  latency p50/p99: {c['latency_steps']['p50']:.0f}/"
          f"{c['latency_steps']['p99']:.0f} steps  "
          f"({c['latency_s_modeled']['p50']*1e3:.0f}/"
          f"{c['latency_s_modeled']['p99']*1e3:.0f} ms modeled)")
    print(f"padded:     {p['tok_per_s']:8.1f} tok/s  "
          f"({p['total_tokens']} tokens)")
    q = result["quantized_kv"]
    print(f"quantized:  {q['tok_per_s']:8.1f} tok/s  "
          f"(kv={q['mode']}, {q['ratio_vs_dense']:.2f}x of dense continuous)")
    s = result["speculative"]
    print(f"speculative:{s['tok_per_s']:8.1f} tok/s  "
          f"(k={s['k']}, acceptance {s['acceptance_rate']:.2f}, "
          f"{s['rounds']} rounds, {s['ratio_vs_dense']:.2f}x of dense)")
    b = result["beam"]
    print(f"beam:       {b['tok_per_s']:8.1f} tok/s  "
          f"(width={b['width']}, {b['ratio_vs_dense']:.2f}x of dense)")
    print(f"ratio continuous/padded: {result['ratio_vs_padded']:.2f}x")

    with open(args.out, "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
    print(f"wrote {args.out}")

    if args.ci and args.baseline:
        with open(args.baseline) as f:
            base = json.load(f)["ratio_vs_padded"]
        floor = base * 0.9
        got = result["ratio_vs_padded"]
        if got < floor:
            print(f"FAIL serving throughput ratio regressed: {got:.2f} < "
                  f"{floor:.2f} (baseline {base:.2f} - 10%)")
            return 1
        print(f"  ok ratio {got:.2f} >= {floor:.2f} "
              f"(baseline {base:.2f} - 10%)")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
