"""Live spans and step scopes: ``Tracer.scope`` records a span and mirrors
it into the jax profiler, ``NullTracer.scope`` costs one shared no-op, the
``burst`` spans count the prompt and output tokens each burst carries (and
the registry's counters agree), and the compiled serving step names its
layers in its ``op_name`` metadata.  Everything runs on virtual clocks."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import transformer as T
from repro.obs import NullTracer, Observability, Tracer
from repro.serving import (DisaggregatedEngineLoop, EngineLoop,
                           prefix_shared_workload, synthetic_workload)
from repro.serving.engine_loop import SlotEngine
from repro.serving.kv_pool import KVPool
from repro.serving.speculative import SpecPlan

TINY = T.ModelConfig(
    name="spans-tiny", n_layers=3, d_model=32, n_heads=4, n_kv_heads=2,
    d_ff=64, vocab=64, attention_impl="dot", remat=False)


@pytest.fixture(scope="module")
def tiny_params():
    return T.init_params(jax.random.PRNGKey(0), TINY)


def _virtual_clock():
    t = [0.0]

    def now():
        t[0] += 1e-3
        return t[0]

    return now


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``."""

    log: list = []

    def __init__(self, name, **args):
        self.name, self.args = name, args

    def __enter__(self):
        self.log.append(("enter", self.name, dict(self.args)))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, dict(self.args)))

    def set_metadata(self, **args):
        self.args.update(args)


# ------------------------------------------------------------ Tracer.scope
def test_scope_records_the_span_inside_a_profiler_annotation(monkeypatch):
    import jax.profiler
    monkeypatch.setattr(_Recorder, "log", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    t = [0.0]
    tr = Tracer(lambda: t[0])
    with tr.scope("burst", track="engine:e", cat="engine",
                  args={"steps": 4}) as note:
        assert _Recorder.log == [("enter", "burst", {"steps": 4})]
        assert tr.n_open == 1 and not tr.spans()
        t[0] = 2.5
        note(deltas=3)
    assert _Recorder.log[-1] == ("exit", "burst", {"steps": 4, "deltas": 3})
    (sp,) = tr.spans("burst")
    assert (sp.ts, sp.dur, sp.cat) == (0.0, 2.5, "engine")
    assert sp.args == {"steps": 4, "deltas": 3}
    assert sp.pid == tr.tracks["engine:e"] and tr.n_open == 0


def test_scope_closes_its_span_when_the_body_raises(monkeypatch):
    import jax.profiler
    monkeypatch.setattr(_Recorder, "log", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    tr = Tracer(lambda: 1.0)
    with pytest.raises(KeyError):
        with tr.scope("iteration", track="server"):
            raise KeyError("stop")
    assert [e[0] for e in _Recorder.log] == ["enter", "exit"]
    assert len(tr.spans("iteration")) == 1 and tr.n_open == 0


def test_null_tracer_scope_is_one_shared_no_op():
    nt = NullTracer()
    a = nt.scope("burst", track="engine:e", args={"steps": 1})
    assert a is nt.scope("sync", track="engine:e")
    with a as note:
        note(deltas=2)
    assert len(nt) == 0 and nt.spans() == []


def test_obs_package_imports_no_jax():
    code = ("import sys; import repro.obs; "
            "from repro.obs import Tracer; "
            "sys.exit('jax' in sys.modules)")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True)
    assert r.returncode == 0, r.stderr.decode()


# --------------------------------------------------- burst token counts
def _run(params, reqs, *, disagg=False, prefix_sharing=False,
         speculative=False):
    obs = Observability(tracer=Tracer())
    if disagg or speculative:
        # self-drafting speculation on the decode engine
        plan = (SpecPlan(draft_cfg=TINY, draft_params=params, k=2)
                if speculative else None)
        loop = DisaggregatedEngineLoop(TINY, params, n_prefill_slots=2,
                                       n_decode_slots=3, max_seq=64,
                                       obs=obs, plan=plan)
    else:
        loop = EngineLoop(TINY, params, n_slots=3, max_seq=64, obs=obs,
                          prefix_sharing=prefix_sharing)
    loop.run(reqs, now_fn=_virtual_clock(), on_delta=lambda d: None)
    return obs


@pytest.mark.parametrize("kind", ["plain", "prefix_shared", "disagg",
                                  "speculative"])
def test_bursts_count_every_prompt_and_output_token(tiny_params, kind):
    if kind == "prefix_shared":
        reqs = prefix_shared_workload(8, rate=1e9, vocab=TINY.vocab,
                                      shared_prefix_len=20, seed=3)
    else:
        reqs = synthetic_workload(9, rate=50.0, vocab=TINY.vocab,
                                  prompt_lens=(4, 8, 13),
                                  gen_lens=(1, 3, 6, 12), seed=11)
    obs = _run(tiny_params, reqs, disagg=kind == "disagg",
               prefix_sharing=kind == "prefix_shared",
               speculative=kind == "speculative")
    tr = obs.tracer
    assert tr.n_open == 0 and tr.n_dropped == 0
    bursts = [s.args for s in tr.spans("burst")]
    total = {k: sum(a[k] for a in bursts)
             for k in ("prefill_tokens", "prefill_steps", "decode_tokens")}
    if kind == "prefix_shared":
        assert sum(r.shared_tokens for r in reqs) > 0
    assert total["prefill_tokens"] == sum(r.prompt_len - r.shared_tokens
                                          for r in reqs)
    assert total["decode_tokens"] == sum(r.max_new_tokens - 1 for r in reqs)
    if kind == "speculative":
        assert any(a.get("speculative") for a in bursts)
    for a in bursts:
        assert 0 <= a["prefill_steps"] <= a["steps"]
        assert a["prefill_steps"] <= a["prefill_tokens"]
        if not a.get("speculative"):     # a round commits what it accepts
            assert (a["prefill_tokens"] + a["decode_tokens"]
                    == a["steps"] * a["n_active"])
    counters = obs.registry.counters
    for k, v in total.items():
        assert counters[f"engine_{k}"].value == v


def test_token_split_of_a_burst():
    eng = SlotEngine.__new__(SlotEngine)     # schedule state only
    eng.prompt_left = np.array([5, 0, 2, 9], np.int64)
    mask = np.array([True, True, True, False])
    # slot 0: 3 prompt tokens; slot 1: 3 output; slot 2: 2 prompt, 1 output
    assert eng.token_split(3, mask) == (5, 3, 4)
    assert eng.token_split(1, np.zeros(4, bool)) == (0, 0, 0)


def test_reserved_unused_gauge_follows_the_pool(tiny_params):
    reqs = synthetic_workload(9, rate=1e9, vocab=TINY.vocab,
                              prompt_lens=(4, 8), gen_lens=(6, 12), seed=5)
    obs = _run(tiny_params, reqs)
    xs = obs.registry.series_values("kv_reserved_unused")
    assert xs and max(xs) > 0 and all(0 <= x <= 1 for x in xs)
    kv = [e.args for e in obs.tracer.events
          if e.ph == "C" and e.name == "kv"]
    assert [a["reserved_unused"] for a in kv] == xs


def test_driver_and_loop_spans_nest_in_each_iteration(tiny_params):
    reqs = synthetic_workload(6, rate=1e9, vocab=TINY.vocab,
                              prompt_lens=(4, 8), gen_lens=(3, 6), seed=2)
    tr = _run(tiny_params, reqs).tracer
    its = tr.spans("iteration")
    assert its and all(s.pid == tr.tracks["server"] for s in its)
    for name in ("admit", "burst", "sync", "emit"):
        spans = tr.spans(name)
        assert spans, name
        for s in spans:
            assert any(i.ts <= s.ts and s.ts + s.dur <= i.ts + i.dur
                       for i in its), name
    assert sum(s.args["admitted"] for s in tr.spans("admit")) == len(reqs)
    assert sum(s.args["deltas"] for s in tr.spans("emit")) > 0
    assert {s.args["kind"] for s in tr.spans("sync")} == {"drain",
                                                          "completion"}


# ------------------------------------------------------ step scopes (HLO)
@pytest.mark.parametrize("layout", ["paged", "dense"])
def test_the_burst_program_names_its_layers(tiny_params, layout):
    eng = SlotEngine(TINY, tiny_params, KVPool(2, 32, block_size=8),
                     kv_layout=layout)
    fn = eng._burst_fn(2)
    text = fn.lower(eng.params, eng.cache, eng._prompts, eng._plens,
                    eng._last_tok, eng._out_buf,
                    jnp.zeros((2,), bool)).compile().as_text()
    for scope in ("embed/", "layer_scan/", "attention/",
                  "attention/kv_write/", "attention/core/", "mlp/", "head/",
                  "feed_sample/", "weight_cast/"):
        assert f"/{scope}" in text, scope
