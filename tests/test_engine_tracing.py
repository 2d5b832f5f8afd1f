"""The serving engine's own instrumentation: named programs, device scopes,
host spans and the host-sync counter, on the CPU smoke model."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as C
from repro.models import lm
from repro.serving.engine import Engine, Request
from repro.serving.strategies import Speculative

SPANS = ("admit", "dispatch", "drain", "drain.poll", "drain.collect")


@pytest.fixture(scope="module")
def smoke():
    cfg = C.get_config("gemma2-27b", smoke=True)
    return cfg, lm.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def top_p_engine(smoke):
    cfg, params = smoke
    return Engine(cfg, None, params, cache_len=64, batch_size=4,
                  temperature=0.7, top_p=0.9, top_p_candidates=8)


def two_requests():
    """Both due at step 0: 3 and 5 tokens out, no EOS."""
    return [(0, Request(prompt=[1, 2, 3, 4], max_new_tokens=3, seed=0)),
            (0, Request(prompt=[9, 8], max_new_tokens=5, seed=1))]


def test_programs_are_named(top_p_engine):
    eng = top_p_engine
    state = eng._fresh_state()
    batch = eng._make_batch(np.zeros((1, 4), np.int32))
    logits1, caches1 = jax.eval_shape(eng._prefill, eng.params, batch)
    scalar = jnp.zeros((), jnp.int32)
    lowered = [
        ("prefill", eng._prefill.lower(eng.params, batch)),
        ("admit", eng._admit_fn.lower(state, caches1, logits1, (),
                                      *[scalar] * 5))]
    lowered += [("decode_loop", loop.lower(eng.params, eng._strategy_params,
                                           state, scalar))
                for loop in eng._loop_fn.values()]
    for name, low in lowered:
        assert f"module @jit_{name} " in low.as_text(), name


def test_decode_loop_scopes(top_p_engine):
    """The compiled loop's op_names carry the sampler's scopes and the
    route of each primitive under them."""
    eng = top_p_engine
    compiled = eng._loop_fn[True].lower(
        eng.params, eng._strategy_params, eng._fresh_state(),
        jnp.zeros((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_decode_loop,")
    names = set(re.findall(r'op_name="([^"]+)"', text))
    under = lambda part: [n for n in names if part in n]  # noqa: E731
    assert under("/sampler/")
    assert under("/sampler/top_k/")
    assert under("/sampler/top_k/top_k.segmented/")
    # the nucleus cut-off's scan runs in the sampler, outside its top_k
    assert [n for n in under("/sampler/scan.batched")
            if "/top_k/" not in n]
    assert not under("@")


def test_host_syncs_counted_exactly(smoke):
    """Hand count for two requests due at step 0 in two free slots:

    * drain before the first dispatch: poll 2 slots (2), none done;
    * dispatch: the budget (1) and the steps run (1); the loop runs until
      both are done (4 steps: the 5-token request's first token came from
      its prefill), as nobody waits for a slot;
    * drain: poll 2 slots (2), both done; the compacted tokens and their
      offsets (2); one sequence log-prob per slot (2).
    """
    cfg, params = smoke
    eng = Engine(cfg, None, params, cache_len=64, batch_size=2)
    recs = eng.serve(two_requests())
    assert [len(r.tokens) for r in recs] == [3, 5]
    st = eng.last_stats
    assert st["loop_dispatches"] == 1 and st["decode_steps"] == 4
    assert st["host_syncs"] == 2 + (1 + 1) + (2 + 2 + 2)
    eng.serve(two_requests())
    assert eng.last_stats["host_syncs"] == 10       # counted per serve


def test_host_syncs_count_strategy_reads(smoke):
    """A strategy's reads count too.  Speculative decoding, same two
    requests: the vanilla count's 10, plus its three meta arrays read per
    finished slot (6) and its three totals read by ``stats`` (3)."""
    cfg, params = smoke
    draft = lm.init_params(jax.random.PRNGKey(7), cfg)
    eng = Engine(cfg, None, params, cache_len=64, batch_size=2,
                 strategy=Speculative(cfg, draft, k=3))
    recs = eng.serve(two_requests())
    assert [len(r.tokens) for r in recs] == [3, 5]
    st = eng.last_stats
    assert st["loop_dispatches"] == 1 and st["spec_rounds"] > 0
    assert st["host_syncs"] == 10 + 2 * 3 + 3


def test_misleading_stats_removed(smoke):
    cfg, params = smoke
    eng = Engine(cfg, None, params, cache_len=64, batch_size=2)
    eng.serve(two_requests())
    for gone in ("prefill_s", "serve_s", "final_step"):
        assert gone not in eng.last_stats
    assert {"decode_s", "decode_tok_per_s", "host_syncs"} <= set(
        eng.last_stats)
    eng.generate_padded([r for _, r in two_requests()])
    assert "prefill_s" not in eng.last_stats
    assert "decode_s" in eng.last_stats


def test_host_spans_in_profiler_trace(smoke, tmp_path):
    """Under a profiler session the serve writes its spans on the host
    plane: one ``admit`` per request with its id, a ``dispatch``, and
    ``drain.poll`` / ``drain.collect`` inside a ``drain``."""
    cfg, params = smoke
    eng = Engine(cfg, None, params, cache_len=64, batch_size=2)
    eng.serve(two_requests())                       # compiles untraced
    with jax.profiler.trace(str(tmp_path)):
        eng.serve(two_requests())
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    spans = {n: [] for n in SPANS}
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        spans[e.name].append(
                            (e.start_ns, e.end_ns, dict(e.stats)))
    assert sorted(s[2]["request_id"] for s in spans["admit"]) == [0, 1]
    assert len(spans["dispatch"]) == 1
    assert len(spans["drain"]) == 2 and len(spans["drain.poll"]) == 2
    assert len(spans["drain.collect"]) == 1

    def inside(child, parents):
        return any(s <= child[0] and child[1] <= e for s, e, _ in parents)

    for child in ("drain.poll", "drain.collect"):
        assert all(inside(c, spans["drain"]) for c in spans[child]), child
    for top in ("admit", "dispatch", "drain"):
        others = [s for n in ("admit", "dispatch", "drain") if n != top
                  for s in spans[n]]
        assert not any(inside(c, others) for c in spans[top]), top
