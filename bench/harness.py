"""The benchmark harness: set-up, the measured window, metrics and checks.

Everything cell-specific is data, found by name:

* ``BENCHMARK.json`` -- the cell's configuration, traffic mix and metrics;
* ``bench/configs/<config>.json`` -- the model as it is run, the engine's
  sizes, the weight dtype and the reference module that fits it;
* ``bench/traffic/<mix>.json`` -- read by the one generator, ``traffic.py``;
* ``bench/cells/<workload>.json`` -- the cell's correctness limits and
  its control's precision;
* ``bench/metrics/<metric>.py`` -- one reader per metric;
* ``bench/references/<name>.py`` -- the plain reference of an architecture.

The window drives the program's own entry point, ``Engine.serve``, on
consecutive chunks of the seeded request stream until ``--seconds`` have
passed; the chunk that crosses the limit runs to its end, and the metrics
use the whole elapsed time.  Each request is timed from when it was due
(the scheduler's ``submit``, called when the engine's step clock reaches
its arrival step) to when it was returned (the end of the ``_drain_done``
that handed back its tokens).  Neither hook syncs the device.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
SPANS = ("_prefill", "_admit_fn", "_dispatch_loop", "_drain_done")
ENGINE_SEED = 0   # the engine's base key is a constant of its programs
WARM_SEED = 1     # the traffic of the warm-up chunks


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def cell_metrics(spec: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports: its end-to-end metrics, or
    with ``trace`` its per-layer metrics (a per-layer metric without a
    ``workloads`` list goes wherever the metric it moves is reported)."""
    e2e = [m for m in spec["end_to_end"] if applies(m, workload)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


class Run:
    """What the metric readers read: counters, request timings, the
    reduced trace, the model and the device's peaks."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


# ---------------------------------------------------------------------------
# The program under test, instrumented from outside.
# ---------------------------------------------------------------------------


def build_engine(model_cfg, params, conf: dict, mix: dict, buckets):
    from repro.serving.engine import Engine
    s = mix["sampling"]
    return Engine(model_cfg, None, params, cache_len=conf["cache_len"],
                  batch_size=conf["batch_size"],
                  temperature=s["temperature"], top_k=s["top_k"],
                  top_p=s["top_p"], top_p_candidates=s["top_p_candidates"],
                  seed=ENGINE_SEED, prefill_buckets=buckets)


def instrument(jax, eng, *, trace: bool):
    """Timing hooks (always) and host spans (traced run only).

    The engine builds its scheduler by the module-level name; a subclass
    stamps each request when it is submitted, i.e. due.  ``_drain_done``
    is wrapped to stamp the requests it completed once it returns."""
    import repro.serving.engine as engine_mod
    from repro.serving.scheduler import Scheduler

    class TimedScheduler(Scheduler):
        def submit(self, request, *, step=0, seed=None):
            rid = super().submit(request, step=step, seed=seed)
            self.records[rid].meta["bench_due"] = time.perf_counter()
            return rid

    engine_mod.Scheduler = TimedScheduler
    drain = eng._drain_done

    def timed_drain(sched, state, now):
        out = drain(sched, state, now)
        t = time.perf_counter()
        for rec in sched.records.values():
            if rec.done and "bench_done" not in rec.meta:
                rec.meta["bench_done"] = t
        return out

    eng._drain_done = timed_drain
    if trace:
        for name in SPANS:
            fn = getattr(eng, name)

            def spanned(*a, _fn=fn, _name=name.lstrip("_"), **k):
                with jax.profiler.TraceAnnotation(_name):
                    return _fn(*a, **k)

            setattr(eng, name, spanned)


def warm_up(eng, conf: dict, buckets, vocab: int):
    """One serve over every shape the cell uses: a prompt inside each
    prefill bucket, and more requests than slots, so the decode loop runs
    both with and without waiters (``stop_on_free`` true and false)."""
    from repro.serving.engine import Request
    rng = np.random.default_rng(0)
    lens = [max(1, (lo + hi) // 2) for lo, hi in zip((0,) + buckets[:-1],
                                                    buckets)]
    lens += [lens[0]] * conf["batch_size"]
    eng.serve([(0, Request(rng.integers(0, vocab, size=n, dtype=np.int32),
                           max_new_tokens=2, seed=i))
               for i, n in enumerate(lens)])


def serve_chunk(eng, items):
    from repro.serving.engine import Request
    recs = eng.serve([(it.arrival_step,
                       Request(it.prompt, max_new_tokens=it.max_new,
                               seed=it.seed)) for it in items])
    out = []
    for it, rec in zip(items, recs):
        out.append({"prompt": it.prompt, "max_new": it.max_new,
                    "arrival_step": it.arrival_step,
                    "submit_step": rec.submit_step,
                    "admit_step": rec.admit_step,
                    "finish_step": rec.finish_step,
                    "tokens": rec.tokens, "seq_logprob": rec.seq_logprob,
                    "due": rec.meta["bench_due"],
                    "done": rec.meta["bench_done"]})
    st = eng.last_stats
    counters = {k: st[k] for k in ("admissions", "loop_dispatches",
                                   "decode_steps", "total_tokens")}
    return out, counters


# ---------------------------------------------------------------------------
# The run.
# ---------------------------------------------------------------------------


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: the configuration's small preset, "
                         "Pallas kernels in interpret mode")
    ap.add_argument("--calibrate", type=int, default=0, metavar="N",
                    help="instead of a run: N seeds from --seed on, each "
                         "one chunk, the program's and the control's "
                         "compared numbers (no metrics)")
    ap.add_argument("--sweep", default="",
                    help="instead of a run: comma-separated arrival rates "
                         "(requests per decode step), two chunks each")
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 3


def main(argv=None, t_start=None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        return fail(f"no workload {args.workload!r} in BENCHMARK.json")
    cell = cells[args.workload]
    conf = load_json(BENCH / "configs" / f"{cell['config']}.json")
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    checking = load_json(BENCH / "cells" / f"{args.workload}.json")
    ref_mod = load_module(BENCH / "references" / f"{conf['reference']}.py")
    metrics = cell_metrics(spec, args.workload, bool(args.trace))
    readers = {m["name"]: load_module(BENCH / "metrics" / f"{m['name']}.py")
               for m in metrics}

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse:
        if device["platform"] != "tpu":
            return fail(f"JAX found no TPU (platform {device['platform']})")
        if device["count"] < cell["chips"]:
            return fail(f"{cell['chips']} chips needed, JAX sees "
                        f"{device['count']}")
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if device["platform"] == "tpu" and device["kind"] not in peaks:
        return fail(f"no peaks for device kind {device['kind']!r}")

    from repro.core import intrinsics as ki
    from repro.launch.compile_cache import enable_compile_cache
    import traffic
    if not args.rehearse:
        enable_compile_cache()
    compiles = watch_compiles(jax)

    if args.rehearse:
        model = conf["rehearse"]["model"]
        econf = conf["rehearse"]
        scale = econf["cache_len"] / conf["cache_len"]
    else:
        model, econf, scale = conf["model"], conf, 1.0
    buckets = traffic.scaled_buckets(mix, scale)
    backend = "pallas-interpret" if args.rehearse else None

    from repro.configs.base import ModelConfig
    model_cfg = ModelConfig(**{k: tuple(v) if isinstance(v, list) else v
                               for k, v in model.items()})
    rng = np.random.default_rng(args.seed % (1 << 64))
    wseed = int(rng.integers(0, 2**31 - 1))

    def ctx():
        return (ki.use_backend(backend) if backend
                else contextlib.nullcontext())

    with ctx():
        params = ref_mod.init_weights(jax.random.PRNGKey(wseed), model)
        jax.block_until_ready(params)
        eng = build_engine(model_cfg, params, econf, mix, buckets)
        instrument(jax, eng, trace=bool(args.trace))
        warm_up(eng, econf, buckets, model["vocab_size"])
        # A rehearsal serves chunks of twice the slots, to stay short.
        n = 2 * econf["batch_size"] if args.rehearse else None
        if args.calibrate:
            del params      # each seed brings its own weights
            return calibrate(jax, eng, args, mix, model, ref_mod, checking,
                             traffic, scale, n)
        if args.sweep:
            return sweep(eng, args, mix, model, traffic, scale)
        # Warm-up chunks of the cell's own traffic.  Where the mix fixes
        # the schedule (a backlog served longest first), every chunk meets
        # the same drain lengths, and the drain compiles a program for
        # each new length: here, not in the window.
        warm = traffic.Stream(mix, model["vocab_size"], WARM_SEED,
                              cache_len=econf["cache_len"], scale=scale, n=n)
        for _ in range(mix.get("warm_chunks", 0)):
            serve_chunk(eng, warm.next_chunk())
        stream = traffic.Stream(mix, model["vocab_size"], args.seed,
                                cache_len=econf["cache_len"], scale=scale,
                                n=n)
        window = measure(jax, eng, stream, args, compiles)
    setup_s = window["t0"] - t_start
    mem = devs[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))

    run = Run(setup_s=setup_s, window_s=window["window_s"],
              requests=window["requests"], counters=window["counters"],
              trace=window.get("trace"), model=model, mix=mix,
              batch_size=econf["batch_size"], chips=cell["chips"],
              peaks=peaks.get(device["kind"]))
    values = {}
    for m in metrics:
        v = readers[m["name"]].read(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s

    # The comparison, once the program's state is gone.
    del eng
    gc.collect()
    t = time.perf_counter()
    with ctx():
        checks, failed = check(window["requests"], rng, params, model, mix,
                               ref_mod, checking)
    c = window["counters"]
    print(f"bench: set-up {setup_s!r} s, window {window['window_s']!r} s "
          f"({len(window['requests'])} requests; {c['compiles']} programs "
          f"compiled in it, {c['compile_s']!r} s), check "
          f"{time.perf_counter() - t!r} s", file=sys.stderr)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": len(window["requests"]),
              "failed": failed, "metrics": values, "device": device}
    if run.trace is not None:
        result["breakdown"] = run.trace.breakdown
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def candidates(mix: dict):
    s = mix["sampling"]
    if s["temperature"] <= 0:
        return None
    if s["top_k"]:
        return s["top_k"]
    return s["top_p_candidates"] if s["top_p"] < 1.0 else None


def nucleus(mix: dict):
    """``(k, temperature, top_p)`` of a sampler with a top-p cut."""
    s = mix["sampling"]
    if s["temperature"] <= 0 or s["top_p"] >= 1.0:
        return None
    return candidates(mix), float(s["temperature"]), float(s["top_p"])


def check(recs, rng, params, model, mix, ref_mod, checking, control=False):
    """The compared numbers of this run, each with its limit, and the
    number of requests that failed.  With ``control``: every number of
    the program and of the control, and the readings."""
    import correctness
    limits = checking["limits"]
    picked = correctness.sample(recs, rng, mix["check_tokens"])
    readings = [correctness.request_readings(
        ref_mod, params, model, r, candidates=candidates(mix),
        nucleus=nucleus(mix), control=control and checking["control"])
        for r in picked]
    nums = correctness.numbers(recs, readings, vocab=model["vocab_size"])
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    if control:
        cnums = correctness.numbers(recs, readings,
                                    vocab=model["vocab_size"], control=True)
        return nums, cnums, readings
    return checks, nums["bad_requests"]


def watch_compiles(jax) -> dict:
    """Count the backend compilations of this process, and their seconds
    (``measure`` reports those that fall inside the window)."""
    seen = {"n": 0, "s": 0.0}

    def listen(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            seen["n"] += 1
            seen["s"] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    return seen


def measure(jax, eng, stream, args, compiles) -> dict:
    """The measured window (and its trace, with ``--trace 1``)."""
    import trace_reduce
    tdir = None
    if args.trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans are TraceMe events
        jax.profiler.start_trace(tdir, profiler_options=opts)
    requests, counters = [], {}
    n0, s0 = compiles["n"], compiles["s"]
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        while True:
            recs, c = serve_chunk(eng, stream.next_chunk())
            requests += recs
            for k, v in c.items():
                counters[k] = counters.get(k, 0) + v
            if time.perf_counter() - t0 >= args.seconds:
                break
    t1 = time.perf_counter()
    counters["compiles"] = compiles["n"] - n0
    counters["compile_s"] = compiles["s"] - s0
    out = {"t0": t0, "window_s": t1 - t0, "requests": requests,
           "counters": counters}
    if tdir is not None:
        jax.profiler.stop_trace()
        if jax.devices()[0].platform == "tpu":
            out["trace"] = trace_reduce.reduce_dir(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
    return out


def calibrate(jax, eng, args, mix, model, ref_mod, checking, traffic,
              scale, n):
    """The program's and the control's compared numbers on ``N`` seeds,
    each with its own weights and one chunk of its own traffic."""
    for seed in range(args.seed, args.seed + args.calibrate):
        rng = np.random.default_rng(seed % (1 << 64))
        wseed = int(rng.integers(0, 2**31 - 1))
        eng.params = None
        gc.collect()
        params = ref_mod.init_weights(jax.random.PRNGKey(wseed), model)
        eng.params = params
        stream = traffic.Stream(mix, model["vocab_size"], seed,
                                cache_len=eng.cache_len, scale=scale, n=n)
        t = time.perf_counter()
        recs, _ = serve_chunk(eng, stream.next_chunk())
        t_serve = time.perf_counter() - t
        t = time.perf_counter()
        nums, cnums, readings = check(recs, rng, params, model, mix,
                                      ref_mod, checking, control=True)
        print(json.dumps({
            "seed": seed, "program": nums, "control": cnums,
            "requests": len(recs), "sampled": len(readings),
            "sampled_tokens": sum(r["n"] for r in readings),
            "serve_s": t_serve, "check_s": time.perf_counter() - t}),
            flush=True)
        del params
    return 0


def sweep(eng, args, mix, model, traffic, scale):
    """Backlog at fixed arrival rates (requests per decode step): for each
    rate, two chunks; per chunk the queue wait in steps of its first and
    last quarter of requests, and the steps the engine ran past the last
    arrival."""
    for rate in [float(r) for r in args.sweep.split(",")]:
        stream = traffic.Stream(mix, model["vocab_size"], args.seed,
                                cache_len=eng.cache_len, scale=scale,
                                rate=rate)
        for chunk in range(2):
            t = time.perf_counter()
            recs, c = serve_chunk(eng, stream.next_chunk())
            wall = time.perf_counter() - t
            wait = np.array([r["admit_step"] - r["submit_step"]
                             for r in recs])
            q = max(1, len(recs) // 4)
            last_arrival = max(r["arrival_step"] for r in recs)
            lat = sorted(r["done"] - r["due"] for r in recs)
            print(json.dumps({
                "rate": rate, "chunk": chunk, "requests": len(recs),
                "wall_s": wall, "req_per_s": len(recs) / wall,
                "tokens_per_s": c["total_tokens"] / wall,
                "wait_first_q": float(wait[:q].mean()),
                "wait_last_q": float(wait[-q:].mean()),
                "wait_max": int(wait.max()),
                "steps_past_last_arrival": int(
                    max(r["finish_step"] for r in recs) - last_arrival),
                "last_arrival_step": last_arrival,
                "decode_steps": c["decode_steps"],
                "latency_p50_s": lat[len(lat) // 2],
                "latency_p95_s": lat[int(0.95 * (len(lat) - 1))]}),
                flush=True)
    return 0
