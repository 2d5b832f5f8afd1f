"""Record the small chip trace of the program's own names that
``test_attribution.py`` reads.

    python bench/tests/record_scoped_trace.py \
        bench/tests/data/deepseek_top_p.xplane.pb

On one TPU: deepseek-coder-33b-8l as ``bench/configs/deepseek-coder-33b-8l
.json`` runs it, sampling as ``bench/traffic/code-sampled.json`` (top-p
over 64 candidates), two requests (prompts of 300 and 700 tokens, 4 and 6
tokens out) served once to warm up and once inside the harness's window
span, with its host spans and trace options.  Writes the trace file to the
path given and prints what the attribution reads in it.
"""
import glob
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)

import jax  # noqa: E402
import numpy as np  # noqa: E402

import attribution  # noqa: E402
import harness  # noqa: E402
import trace_reduce  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.serving.engine import Request  # noqa: E402


def main(out):
    assert jax.devices()[0].platform == "tpu", "needs a TPU"
    conf = harness.load_json(
        harness.BENCH / "configs" / "deepseek-coder-33b-8l.json")
    mix = harness.load_json(harness.BENCH / "traffic" / "code-sampled.json")
    ref = harness.load_module(harness.BENCH / "references" / "dense_gqa.py")
    m = conf["model"]
    cfg = ModelConfig(**{k: tuple(v) if isinstance(v, list) else v
                         for k, v in m.items()})
    params = ref.init_weights(jax.random.PRNGKey(0), m)
    eng = harness.build_engine(cfg, params, conf, mix, (1024,))
    harness.instrument(jax, eng, trace=True)
    rng = np.random.default_rng(0)

    def serve():
        eng.serve([(0, Request(rng.integers(0, m["vocab_size"], size=n,
                                            dtype=np.int32),
                               max_new_tokens=k, seed=i))
                   for i, (n, k) in enumerate(((300, 4), (700, 6)))])

    serve()
    tdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        serve()
    jax.profiler.stop_trace()
    (f,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(f, out)
    shutil.rmtree(tdir)
    s = trace_reduce.reduce_file(out)
    a = attribution.attribute_file(out)
    print(os.path.getsize(out), s.window_s, s.busy_s, s.programs,
          eng.last_stats)
    print(a.tables())


if __name__ == "__main__":
    main(sys.argv[1])
