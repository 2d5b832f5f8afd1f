"""Bring-up smoke on a TPU: the primitives and the serving engine, end to end.

    python chip_smoke.py               # one chip: phases 1-3
    python chip_smoke.py --four-chip   # four chips: the @sharded routes only

Phase 1 checks the device, phase 2 runs the Pallas primitives at the paper's
sizes against XLA-native ops, phase 3 serves the full minitron-4b (32
layers, published widths, random bf16 weights from ``--seed``) through
``Engine.generate`` / ``Engine.serve``.  ``--four-chip`` runs only the
device-spanning routes and the tensor-parallel unembed, each against the
same work on one device.

Every check that fails exits non-zero; no phase catches its own failure.
The script refuses to run without a TPU (it never falls back to the CPU).
Times printed are smoke timings of one cold run, compilation included; they
are not benchmark metrics.  The last line of standard output is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
# The autotuner skips candidates that raise; nothing here may hide a kernel
# that fails to compile behind it.
os.environ.pop("REPRO_AUTOTUNE", None)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import intrinsics as ki  # noqa: E402
from repro.core import operators as alg  # noqa: E402
from repro.core import primitives as forge  # noqa: E402
from repro.core.layout import Batched, Segmented, Sharded  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serving.engine import Engine, Request  # noqa: E402

KERNEL = "tpu_custom_call"
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "all-to-all", "reduce-scatter")


def check(ok, what: str):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def compiled_call(fn, *args, backend: str, what: str):
    """jit-compile ``fn``; on pallas-tpu the Mosaic kernel must be in the
    program (the registry's xla fallback compiles too).  Returns (compiled
    program, its HLO text)."""
    c = jax.jit(fn).lower(*args).compile()
    text = c.as_text()
    if backend == "pallas-tpu":
        check(KERNEL in text, f"{what}: no {KERNEL} in the compiled HLO")
    return c, text


def max_err(got, want) -> float:
    got = np.asarray(jnp.asarray(got, jnp.float32), np.float64)
    want = np.asarray(jnp.asarray(want, jnp.float32), np.float64)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    return float(np.max(np.abs(got - want))) if got.size else 0.0


def report(route, shape, err, hlo: str, t0):
    print(f"  {route:<34} shape={shape} max_err={err!r} "
          f"kernels={hlo.count(KERNEL)} s={time.perf_counter() - t0!r}",
          flush=True)


# ---------------------------------------------------------------------------
# Phase 1: the device.
# ---------------------------------------------------------------------------


def device_info() -> dict:
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    print(f"phase 1: device {json.dumps(info)} "
          f"detect_chip={ki.detect_chip()} "
          f"current_backend={ki.current_backend()}", flush=True)
    return info


def require_tpu(info: dict, chips: int):
    check(info["platform"] == "tpu",
          f"JAX found no TPU (platform {info['platform']!r})")
    check(info["count"] >= chips,
          f"{chips} chips needed, JAX sees {info['count']}")
    check(ki.current_backend() == "pallas-tpu",
          f"default backend is {ki.current_backend()}, not pallas-tpu")
    if "v5 lite" in info["kind"].lower():
        check(ki.detect_chip() == "tpu_v5e",
              f"a v5e detected as {ki.detect_chip()}")


# ---------------------------------------------------------------------------
# Phase 2: the primitives at the paper's sizes.
# ---------------------------------------------------------------------------


def primitives(key, *, n, mv, topk_rows, vocab, k, backend):
    """Each Pallas route against XLA-native / the xla route.  Sums and
    scans run on integer-valued floats, where f32 arithmetic is exact, so
    any difference is a wrong result, not rounding."""
    print(f"phase 2: primitives on {backend}", flush=True)
    ks = jax.random.split(key, 6)
    ints = jax.random.randint(ks[0], (n,), -8, 9).astype(jnp.float32)

    t0 = time.perf_counter()
    c, text = compiled_call(
        lambda x: forge.scan(alg.ADD, x, backend=backend),
        ints, backend=backend, what="scan@flat")
    err = max_err(c(ints), jnp.cumsum(ints))
    check(err == 0.0, f"scan@flat differs from cumsum by {err}")
    report("scan@flat ADD f32", (n,), err, text, t0)

    t0 = time.perf_counter()
    c, text = compiled_call(
        lambda x: forge.mapreduce(lambda v: v, alg.ADD, x, backend=backend),
        ints, backend=backend, what="mapreduce@flat")
    err = max_err(c(ints), jnp.sum(ints))
    check(err == 0.0, f"mapreduce@flat differs from sum by {err}")
    report("mapreduce@flat ADD f32", (n,), err, text, t0)

    t0 = time.perf_counter()
    u8 = jax.random.randint(ks[1], (n,), 0, 256).astype(jnp.uint8)
    c, text = compiled_call(
        lambda u: forge.mapreduce(alg.unitfloat8_decode, alg.ADD, u,
                                  backend=backend),
        u8, backend=backend, what="mapreduce@flat unitfloat8")
    want = np.sum(np.asarray(u8, np.float64) * (2.0 / 255.0) - 1.0)
    err = max_err(c(u8), want)
    # f32 accumulation of n values in [-1, 1]: far below n * 2^-23.
    check(err <= n * 2.0 ** -23, f"unitfloat8 mapreduce off by {err}")
    report("mapreduce@flat UnitFloat8->f32", (n,), err, text, t0)

    t0 = time.perf_counter()
    A = jax.random.normal(ks[2], (mv, mv), jnp.bfloat16)
    x = jax.random.normal(ks[3], (mv,), jnp.bfloat16)
    c, text = compiled_call(
        lambda A, x: forge.matvec(lambda x_i, a: x_i * a, alg.ADD, A, x,
                                  backend=backend),
        A, x, backend=backend, what="matvec@flat")
    ref = jnp.einsum("n,np->p", x, A, preferred_element_type=jnp.float32)
    scale = float(jnp.max(jnp.abs(ref)))
    err = max_err(c(A, x), ref)
    err_xla = max_err(jnp.einsum("n,np->p", x, A), ref)
    # bf16 output: the XLA einsum itself rounds to ~2^-8 of the scale.
    check(err <= 2.0 ** -4 * scale,
          f"matvec@flat off by {err} (scale {scale}, xla {err_xla})")
    report("matvec@flat bf16", (mv, mv), err, text, t0)

    t0 = time.perf_counter()
    xb = jax.random.randint(ks[4], (8, 64), -8, 9).astype(jnp.float32)
    c, text = compiled_call(
        lambda v: forge.scan(alg.ADD, v, inclusive=False, layout=Batched(),
                             backend=backend),
        xb, backend=backend, what="scan@batched")
    err = max_err(c(xb), jnp.cumsum(xb, axis=1) - xb)
    check(err == 0.0, f"scan@batched differs by {err}")
    report("scan@batched ADD f32 exclusive", (8, 64), err, text, t0)

    t0 = time.perf_counter()
    logits = jax.random.normal(ks[5], (topk_rows, vocab), jnp.float32)
    offsets = jnp.arange(topk_rows + 1, dtype=jnp.int32) * vocab
    c, text = compiled_call(
        lambda f: forge.top_k(f, k, layout=Segmented(offsets=offsets),
                              backend=backend),
        logits.reshape(-1), backend=backend, what="top_k@segmented")
    vals, idx = c(logits.reshape(-1))
    want_v, want_i = jax.lax.top_k(logits, k)
    err = max_err(vals, want_v)
    check(err == 0.0, f"top_k@segmented values differ by {err}")
    check(bool(jnp.all(idx == want_i)), "top_k@segmented indices differ")
    report("top_k@segmented f32", (topk_rows, vocab), err, text, t0)

    # The serving loop's own calls: its condition and the drain.
    t0 = time.perf_counter()
    flags = jnp.asarray([1, 0, 0, 1, 0, 0, 0, 0], jnp.int32)
    c, text = compiled_call(
        lambda a: forge.mapreduce(lambda v: v, alg.MAX, a, backend=backend),
        flags, backend=backend, what="mapreduce@flat MAX (loop condition)")
    err = max_err(c(flags), 1)
    check(err == 0.0, "loop-condition mapreduce")
    report("mapreduce@flat MAX i32", (8,), err, text, t0)
    t0 = time.perf_counter()
    c, text = compiled_call(
        lambda a: forge.scan(alg.ADD, a, backend=backend),
        flags, backend=backend, what="scan@flat (drain)")
    err = max_err(c(flags), jnp.cumsum(flags))
    check(err == 0.0, "drain scan")
    report("scan@flat ADD i32", (8,), err, text, t0)


# ---------------------------------------------------------------------------
# Phase 3: the serving engine at full size.
# ---------------------------------------------------------------------------


def _prompts(rng, lengths, vocab):
    return [rng.integers(0, vocab, size=L).tolist() for L in lengths]


def _loop_kernels(eng, backend, what):
    """Lower the engine's decode loop: on pallas-tpu it must hold Mosaic
    kernels (the loop condition, and the sampler when sampling)."""
    state = jax.eval_shape(eng._fresh_state)
    budget = jax.ShapeDtypeStruct((), jnp.int32)
    text = eng._loop_fn[True].lower(eng.params, eng._strategy_params, state,
                                    budget).as_text()
    n = text.count(KERNEL)
    if backend == "pallas-tpu":
        check(n > 0, f"{what}: decode loop holds no {KERNEL}")
    return n


def _tie_report(eng, reqs, out, oracle, first):
    """At the first token where ``generate`` and ``generate_padded`` part,
    print the f32 logits of both choices and the top-2 gap, so a near-tie
    that two batch shapes round apart can be told from a real fault."""
    i, j = first
    toks = np.asarray([reqs[i].prompt + out[i][:j]], np.int32)
    logits, _ = eng._prefill(eng.params, eng._make_batch(toks))
    row = np.asarray(logits[0], np.float64)
    top2 = np.sort(row)[-2:]
    a = out[i][j] if j < len(out[i]) else None
    b = oracle[i][j] if j < len(oracle[i]) else None
    print(f"  first difference: request {i} token {j}: generate {a} "
          f"(logit {None if a is None else float(row[a])!r}), generate_padded "
          f"{b} (logit {None if b is None else float(row[b])!r}); f32 top-2 "
          f"{float(top2[1])!r} {float(top2[0])!r} "
          f"gap {float(top2[1] - top2[0])!r}",
          flush=True)


def serving(key, seed, *, cfg, cache_len, batch, short, long, max_new,
            n_serve, backend):
    print(f"phase 3: serving {cfg.name} ({cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}) on {backend}", flush=True)
    V = cfg.vocab_size
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    params = jax.jit(lambda k: lm.init_params(k, cfg, jnp.bfloat16))(key)
    jax.block_until_ready(params)
    print(f"  params {lm.count_params(params)} bf16 built on device "
          f"s={time.perf_counter() - t0!r}", flush=True)

    with ki.use_backend(backend):
        # (a) greedy: continuous batching against the padded oracle.  The
        # oracle left-pads ragged prompts (pads attend), so it is run per
        # group of equal-length prompts.
        t0 = time.perf_counter()
        eng = Engine(cfg, None, params, cache_len=cache_len,
                     batch_size=batch)
        half = batch // 2
        reqs = [Request(p, max_new_tokens=max_new) for p in _prompts(
            rng, [short] * half + [long] * (batch - half), V)]
        out = eng.generate(reqs)
        stats = dict(eng.last_stats)
        again = eng.generate(reqs)
        oracle = (eng.generate_padded(reqs[:half])
                  + eng.generate_padded(reqs[half:]))
        check(all(len(o) == max_new for o in out), "greedy token counts")
        check(out == again, "greedy generate differs on a second call")
        # (request, first token that differs) for every mismatch.
        diff = [(i, next((j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                         min(len(a), len(b))))
                for i, (a, b) in enumerate(zip(out, oracle)) if a != b]
        if diff:
            _tie_report(eng, reqs, out, oracle, diff[0])
        check(not diff, f"generate != generate_padded at {diff}")
        n_k = _loop_kernels(eng, backend, "greedy")
        print(f"  generate greedy: {batch} requests (prompts {short}/{long}, "
              f"max_new {max_new}) == generate_padded, repeatable; "
              f"loop kernels={n_k} prefill_s={stats['prefill_s']!r} "
              f"decode_s={stats['decode_s']!r} "
              f"s={time.perf_counter() - t0!r}", flush=True)
        del eng

        # (b) sampled serving over an open-loop arrival trace, more
        # requests than slots: admission, eviction and the radix top-k
        # sampler all run.
        t0 = time.perf_counter()
        eng = Engine(cfg, None, params, cache_len=cache_len,
                     batch_size=batch, temperature=0.8, top_k=50, top_p=0.9,
                     seed=seed)
        lengths = rng.choice([short // 2, short, long // 2], size=n_serve)
        budgets = rng.integers(max_new // 4, max_new + 1, size=n_serve)
        steps = np.cumsum(rng.exponential(4.0, size=n_serve)).astype(int)
        arrivals = [(int(s), Request(p, max_new_tokens=int(m)))
                    for s, p, m in zip(steps, _prompts(rng, lengths, V),
                                       budgets)]
        recs = eng.serve(arrivals)
        check(len(recs) == n_serve, f"{len(recs)} of {n_serve} finished")
        for (_, r), rec in zip(arrivals, recs):
            check(len(rec.tokens) == r.max_new_tokens,
                  f"request got {len(rec.tokens)} of {r.max_new_tokens}")
            check(all(0 <= t < V for t in rec.tokens), "token id out of range")
            check(np.isfinite(rec.seq_logprob), "non-finite seq_logprob")
        n_k = _loop_kernels(eng, backend, "sampled")
        st = eng.last_stats
        print(f"  serve sampled: {n_serve} requests on {batch} slots, "
              f"{st['admissions']} admissions, {st['loop_dispatches']} loop "
              f"dispatches, {st['total_tokens']} tokens, loop kernels={n_k} "
              f"s={time.perf_counter() - t0!r}", flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"  peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}", flush=True)


# ---------------------------------------------------------------------------
# --four-chip: the device-spanning routes.
# ---------------------------------------------------------------------------


def _split_over(text, arr, mesh, what):
    """The program is partitioned over every device of the mesh, exchanges
    partials through a collective, and its input lives in equal shards."""
    n = mesh.devices.size
    check(f"num_partitions={n}" in text, f"{what}: not partitioned {n} ways")
    check(any(c in text for c in COLLECTIVES), f"{what}: no collective")
    shards = arr.addressable_shards
    check(len({s.device for s in shards}) == n
          and all(s.data.shape[0] * n == arr.shape[0] for s in shards),
          f"{what}: input is not split over {n} devices")


def four_chip(key, *, n, mv, lr, cfg, backend):
    from jax.sharding import NamedSharding, PartitionSpec as P

    devs = jax.devices()[:4]
    mesh = make_mesh((4,), ("model",))
    lo = Sharded("model", mesh=mesh)
    split = NamedSharding(mesh, P("model"))
    on0 = jax.sharding.SingleDeviceSharding(devs[0])
    print(f"four-chip: @sharded routes on {backend} over {mesh}",
          flush=True)
    ks = jax.random.split(key, 6)

    def both(route, fn, args, specs):
        """The sharded route and the same route on one device."""
        t0 = time.perf_counter()
        sharded = [jax.device_put(a, s) for a, s in zip(args, specs)]
        c, text = compiled_call(lambda *a: fn(lo, *a), *sharded,
                                backend=backend, what=route)
        _split_over(text, sharded[0], mesh, route)
        got = c(*sharded)
        local = [jax.device_put(a, on0) for a in args]
        c1, _ = compiled_call(lambda *a: fn(None, *a), *local,
                              backend=backend, what=route + " (1 device)")
        return got, c1(*local), text, t0

    ints = jax.random.randint(ks[0], (n,), -8, 9).astype(jnp.float32)
    got, want, text, t0 = both(
        "scan@sharded", lambda lay, x: forge.scan(alg.ADD, x, layout=lay,
                                                  backend=backend),
        [ints], [split])
    err = max_err(got, want)
    check(err == 0.0, f"scan@sharded differs by {err}")
    report("scan@sharded ADD f32", (n,), err, text, t0)

    got, want, text, t0 = both(
        "mapreduce@sharded",
        lambda lay, x: forge.mapreduce(lambda v: v, alg.ADD, x, layout=lay,
                                       backend=backend),
        [ints], [split])
    err = max_err(got, want)
    check(err == 0.0, f"mapreduce@sharded differs by {err}")
    report("mapreduce@sharded ADD f32", (n,), err, text, t0)

    A = jax.random.normal(ks[1], (mv, mv), jnp.float32)
    x = jax.random.normal(ks[2], (mv,), jnp.float32)
    got, want, text, t0 = both(
        "matvec@sharded",
        lambda lay, A, x: forge.matvec(lambda x_i, a: x_i * a, alg.ADD, A, x,
                                       layout=lay, backend=backend),
        [A, x], [NamedSharding(mesh, P("model", None)), split])
    err = max_err(got, want)
    check(err <= 1e-4 * float(jnp.max(jnp.abs(want))),
          f"matvec@sharded off by {err}")
    report("matvec@sharded f32", (mv, mv), err, text, t0)

    Bl, T, Cl = lr
    a = jax.random.uniform(ks[3], (Bl, T, Cl), jnp.float32, 0.5, 1.0)
    b = jax.random.normal(ks[4], (Bl, T, Cl), jnp.float32)
    tsplit = NamedSharding(mesh, P(None, "model", None))

    def lin(lay, a, b):
        return forge.linear_recurrence(a, b, layout=lay, backend=backend)

    t0 = time.perf_counter()
    sa, sb = jax.device_put(a, tsplit), jax.device_put(b, tsplit)
    c, text = compiled_call(lambda a, b: lin(lo, a, b), sa, sb,
                            backend=backend, what="linear_recurrence@sharded")
    check("num_partitions=4" in text and
          any(k in text for k in COLLECTIVES),
          "linear_recurrence@sharded is not split over 4 devices")
    check(len({s.device for s in sa.addressable_shards}) == 4,
          "linear_recurrence input not split")
    got = c(sa, sb)
    c1, _ = compiled_call(lambda a, b: lin(None, a, b),
                          jax.device_put(a, on0), jax.device_put(b, on0),
                          backend=backend, what="linear_recurrence (1 device)")
    want = c1(jax.device_put(a, on0), jax.device_put(b, on0))
    err = max_err(got, want)
    check(err <= 1e-4 * float(jnp.max(jnp.abs(want))),
          f"linear_recurrence@sharded off by {err}")
    report("linear_recurrence@sharded f32", (Bl, T, Cl), err, text, t0)

    # decode_step's logit GEMV through matvec@sharded (tp_unembed) against
    # the dense unembed, at minitron-4b's widths with its depth cut to 2.
    t0 = time.perf_counter()
    params = jax.jit(lambda k: lm.init_params(k, cfg, jnp.bfloat16),
                     out_shardings=NamedSharding(mesh, P()))(ks[5])
    B, S, L = 8, 16, 64
    toks = jax.random.randint(ks[5], (B, S), 0, cfg.vocab_size, jnp.int32)
    _, caches = jax.jit(lambda p, t: lm.prefill(p, cfg, t, cache_len=L))(
        params, toks)
    tok = toks[:, -1:]
    pos = jnp.asarray(S, jnp.int32)
    with ki.use_backend(backend):
        c, text = compiled_call(
            lambda p, cc, t, q: lm.decode_step(p, cfg, cc, t, q,
                                               tp_unembed=(mesh, "model")),
            params, caches, tok, pos, backend=backend, what="tp_unembed")
        check("num_partitions=4" in text and "all-reduce" in text,
              "tp_unembed decode step is not split over 4 devices")
        got, _ = c(params, caches, tok, pos)
        p0, c0 = jax.device_put((params, caches), on0)
        want, _ = jax.jit(lambda p, cc, t, q: lm.decode_step(
            p, cfg, cc, t, q))(p0, c0, jax.device_put(tok, on0), pos)
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = max_err(got, want)
    # bf16 activations: strip partial sums round differently than the dense
    # GEMV; the argmax must agree wherever the top two logits are apart.
    check(err <= 2.0 ** -4 * float(np.max(np.abs(want))),
          f"tp_unembed logits off by {err}")
    top2 = -np.sort(-want, axis=-1)[:, :2]
    clear = top2[:, 0] - top2[:, 1] > 2 * err
    same = np.argmax(got, -1) == np.argmax(want, -1)
    check(bool(np.all(same[clear])), "tp_unembed changes the greedy token")
    report("decode_step tp_unembed", (B, cfg.d_model, cfg.vocab_size), err,
           text, t0)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the @sharded routes over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    info = device_info()
    require_tpu(info, 4 if args.four_chip else 1)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    key = jax.random.PRNGKey(args.seed)
    cfg = get_config("minitron-4b")
    t0 = time.perf_counter()
    if args.four_chip:
        four_chip(key, n=10**7, mv=4096, lr=(2, 4096, 256),
                  cfg=dataclasses.replace(cfg, n_layers=2, n_units=2),
                  backend="pallas-tpu")
    else:
        t1 = time.perf_counter()
        primitives(key, n=10**7, mv=4096, topk_rows=8, vocab=cfg.vocab_size,
                   k=50, backend="pallas-tpu")
        print(f"phase 2 s={time.perf_counter() - t1!r}", flush=True)
        t1 = time.perf_counter()
        serving(key, args.seed, cfg=cfg, cache_len=2048, batch=8, short=128,
                long=1024, max_new=32, n_serve=16, backend="pallas-tpu")
        print(f"phase 3 s={time.perf_counter() - t1!r}", flush=True)
    print(f"total s={time.perf_counter() - t0!r} (smoke timings, not "
          f"metrics)", flush=True)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
