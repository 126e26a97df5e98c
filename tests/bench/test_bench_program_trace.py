"""The reduction of the program's scopes and spans: device time by
model-step scope, device idle time by the innermost program span, and the
engine loop's readings from the program's tracer ring."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import program_trace as pt  # noqa: E402
from bench.trace import OPS_LINE, Event  # noqa: E402
from repro.obs import Tracer  # noqa: E402

DEV = "/device:TPU:0"
STEP = "jit(burst)/while/body/closed_call/while/body/closed_call"


@pytest.mark.parametrize("path,scope", [
    (f"{STEP}/attention/dot_general", "attention"),
    (f"{STEP}/attention/kv_write/scatter", "attention/kv_write"),
    (f"{STEP}/attention/core/jit(paged_gather)/gather", "attention/core"),
    (f"{STEP}/attention/weight_cast/convert_element_type", "weight_cast"),
    (f"{STEP}/mlp/weight_cast/convert_element_type", "weight_cast"),
    (f"{STEP}/mlp/dot_general", "mlp"),
    # a block inside the layer scan is its block; the scan's own slicing
    # of the stacked parameters and cache is layer_scan
    ("jit(burst)/while/body/closed_call/layer_scan/while/body/closed_call"
     "/attention/core/gather", "attention/core"),
    ("jit(burst)/while/body/closed_call/layer_scan/while/body/"
     "dynamic_update_slice", "layer_scan"),
    ("jit(burst)/while/body/closed_call/head/dot_general", "head"),
    ("jit(burst)/while/body/closed_call/embed/jit(_take)/gather", "embed"),
    ("jit(burst)/while/body/closed_call/feed_sample/argmax", "feed_sample"),
    ("jit(burst)/while", pt.UNSCOPED),
    ("", pt.UNSCOPED),
])
def test_scope_of_an_op_name(path, scope):
    assert pt.scope_of(path) == scope


# the shapes of a compiled step (v5e): a parameter's cast the compiler
# hoisted out of the layer scan keeps no op_name, nor do the converts and
# relayout copies it adds; a fusion's op_name is inside its computation
HLO = """HloModule jit_burst, entry_computation_layout={()}

%fused_computation.7 (param_0.1: f32[4]) -> bf16[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  %convert.3 = bf16[4]{0} convert(%param_0.1), metadata={op_name="a/mlp/dot_general" stack_frame_id=3}
  ROOT %bitcast.4 = bf16[4]{0} bitcast(%convert.3)
}

ENTRY %main.9 (p: f32[28,4], q: f32[8], c: bf16[4]) -> bf16[4] {
  %p__w__.1 = f32[28,4]{1,0:T(8,128)} parameter(0), metadata={op_name="p['w']"}
  %p__e__.1 = f32[8]{0} parameter(1), metadata={op_name="p['e']"}
  %c__k__.1 = bf16[4]{0} parameter(2)
  %convert.42 = bf16[28,4]{1,0:T(8,128)(2,1)} convert(%p__w__.1), backend_config={"flag_configs":[]}
  %copy-start.10 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(%p__e__.1)
  %copy-done.10 = f32[8]{0:S(1)} copy-done(%copy-start.10)
  %copy.35 = bf16[8]{0} copy(%copy-done.10), metadata={op_name="p['e']"}
  %copy.83 = bf16[4]{0} copy(%c__k__.1)
  %fusion.6 = bf16[4]{0} fusion(%copy.83), kind=kLoop, calls=%fused_computation.7
  %convert.124 = f32[4]{0} convert(%fusion.6)
  %bitcast.222 = f32[2,2]{1,0} bitcast(%convert.124)
  %copy.79 = f32[2,2]{0,1} copy(%bitcast.222)
  %get-tuple-element.5 = bf16[4]{0} get-tuple-element(%tuple.1), index=0, metadata={op_name="a/layer_scan/while"}
  %copy.84 = bf16[4]{0} copy(%get-tuple-element.5)
  ROOT %dot.8 = bf16[4]{0} multiply(%fusion.6, %fusion.6), metadata={op_name="a/head/dot_general"}
}
"""


def test_op_paths_from_compiled_hlo_text():
    paths = pt.op_paths_from_hlo(HLO)
    assert paths["dot.8"] == "a/head/dot_general"
    # a fusion without metadata takes what its fused computation holds
    assert paths["fusion.6"] == "a/mlp/dot_general"
    # hoisted casts of parameters, directly or through an async copy
    assert paths["convert.42"] == "weight_cast"
    assert paths["copy.35"] == "weight_cast"
    # a value the compiler converts, relayouts or copies out of the layer
    # scan keeps the op_name of what it moves
    assert paths["copy.79"] == paths["convert.124"] == "a/mlp/dot_general"
    assert paths["copy.84"] == "a/layer_scan/while"
    # a copy of the cache that keeps its dtype is no cast
    assert paths["copy.83"] == ""
    assert {pt.scope_of(paths[n]) for n in ("copy.83", "copy-done.10",
                                            "c__k__.1")} == {pt.UNSCOPED}


def _xspace(ops, spans):
    """A trace as the profiler writes it: ``ops`` ``(module, hlo, start,
    dur)`` on a device (a module is ``(name, start, dur)``), ``spans``
    ``(name, start, dur, {stat: value})`` on the host; times in ns."""
    from jax.profiler import ProfileData
    names: dict = {}

    def md(name):
        return names.setdefault(name, len(names) + 1)

    mods = {m for m, *_ in ops if m is not None}
    dev_mods = "".join(
        f"events {{ metadata_id: {md(n)} offset_ps: {int(s * 1e3)} "
        f"duration_ps: {int(d * 1e3)} }}" for n, s, d in sorted(mods))
    dev_ops = "".join(
        f"events {{ metadata_id: {md(h)} offset_ps: {int(s * 1e3)} "
        f"duration_ps: {int(d * 1e3)} }}" for _, h, s, d in ops)
    stat_ids: dict = {}
    host = ""
    for n, s, d, stats in spans:
        st = "".join(
            f"stats {{ metadata_id: "
            f"{stat_ids.setdefault(k, len(stat_ids) + 1)} "
            f"str_value: \"{v}\" }}" for k, v in stats.items())
        host += (f"events {{ metadata_id: {md(n)} offset_ps: {int(s * 1e3)} "
                 f"duration_ps: {int(d * 1e3)} {st} }}")
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                   f'"{n}" }} }}' for n, i in names.items())
    smeta = "".join(f'stat_metadata {{ key: {i} value {{ id: {i} name: '
                    f'"{n}" }} }}' for n, i in stat_ids.items())
    return ProfileData.from_text_proto(
        f'planes {{ id: 1 name: "{DEV}" '
        f'lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {dev_mods} }} '
        f'lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {dev_ops} }} '
        f'{meta} }} '
        f'planes {{ id: 2 name: "/host:CPU" '
        f'lines {{ id: 3 name: "python3" timestamp_ns: 0 {host} }} '
        f'{meta} {smeta} }}')


def test_scoped_events_read_each_step_program_with_its_own_names():
    one = {"fusion.1": f"{STEP}/attention/x", "copy.2": "",
           "fusion.9": f"{STEP}/mlp/x"}
    four = {"fusion.1": f"{STEP}/mlp/x", "while.3": "jit(burst)/while",
            "fusion.4": f"{STEP}/head/x"}
    b1, b4 = ("jit_burst(11)", 0, 100), ("jit_burst(44)", 200, 100)
    profile = _xspace(
        [(b1, "%fusion.1 = bf16[4] fusion(%p.1)", 10, 20),
         (b1, "%copy.2 = bf16[4] copy(%c.1)", 40, 10),
         (b4, "%while.3 = (s32[]) while(%t.1)", 200, 100),
         (b4, "%fusion.1 = bf16[4] fusion(%p.1)", 210, 30),
         (b4, "%fusion.4 = bf16[4] fusion(%q.1)", 250, 10),
         (None, "%fusion.4 = bf16[4] scatter(%q.1)", 150, 5)],
        [("burst", 0, 5, {"steps": "1"}), ("sync", 100, 50,
                                            {"kind": "drain"}),
         ("dispatch", 160, 10, {}), ("emit", 160, 5, {})])
    ops, spans = pt.scoped_events(profile, [one, four])
    # fusion.1 is attention in the one-step program, mlp in the other; an
    # operation outside the step programs is unscoped whatever its name
    assert [(e.name, e.start_ns, e.dur_ns) for e in ops] == [
        ("attention", 10.0, 20.0), (pt.UNSCOPED, 40.0, 10.0),
        (pt.UNSCOPED, 200.0, 100.0), ("mlp", 210.0, 30.0),
        ("head", 250.0, 10.0), (pt.UNSCOPED, 150.0, 5.0)]
    # the host's own profiler events are not program spans
    assert [(s.name, s.start_ns, s.dur_ns) for s in spans] == [
        ("burst", 0.0, 5.0), ("sync.drain", 100.0, 50.0),
        ("emit", 160.0, 5.0)]
    assert pt.burst_steps(spans) == 1


def _op(scope, start, dur):
    return Event(DEV, OPS_LINE, scope, float(start), float(dur))


# a while (unscoped) holding a burst's body, an idle gap, another burst
OPS = [
    _op(pt.UNSCOPED, 0, 100),            # while: 0-100
    _op("attention/kv_write", 10, 10),
    _op("attention", 20, 20),
    _op("attention/core", 40, 30),
    _op("mlp", 70, 20),
    _op("weight_cast", 150, 25),         # 150-175
    _op("head", 180, 20),                # 180-200
]
SPANS = [
    pt.span("iteration", 90, 100),       # 90-190
    pt.span("sync", 95, 10, (("kind", "drain"),)),      # 95-105
    pt.span("emit", 105, 15),            # 105-120
    pt.span("admit", 125, 10),           # 125-135
    pt.span("burst", 140, 15, (("steps", "4"),)),       # 140-155
]


def test_device_scopes_split_busy_time_by_scope():
    s = pt.device_scopes(OPS)
    assert s == pytest.approx({
        pt.UNSCOPED: 20e-9, "attention": 60e-9, "attention/kv_write": 10e-9,
        "attention/core": 30e-9, "mlp": 20e-9, "weight_cast": 25e-9,
        "head": 20e-9})
    busy = 100 + 25 + 20
    assert sum(v for k, v in s.items() if "/" not in k) == pytest.approx(
        busy * 1e-9)


def test_idle_by_span_names_the_innermost_span_over_each_part():
    idle = pt.idle_by_span(OPS, SPANS)
    # idle 100-150, 175-180: drain 100-105, emit 105-120, iteration
    # 120-125 and 135-140, admit 125-135, burst 140-150, iteration 175-180
    assert idle == pytest.approx({
        "sync.drain": 5e-9, "emit": 15e-9, "iteration": 15e-9,
        "admit": 10e-9, "burst": 10e-9})
    assert pt.burst_steps(SPANS) == 4
    # a window past the spans: the part under none goes to (none)
    wider = pt.idle_by_span(OPS, SPANS, window_ns=(0, 230))
    assert wider[pt.NO_SPAN] == pytest.approx(30e-9)
    assert sum(wider.values()) == pytest.approx((230 - 145) * 1e-9)


# ---- readings of the program's tracer ring --------------------------------

def _ring(bursts, syncs, capacity=65536):
    """A tracer holding ``burst`` spans ``(t0, t1, prefill_tokens,
    prefill_steps)`` and ``sync`` spans ``(t0, t1)``."""
    tr = Tracer(lambda: 0.0, capacity=capacity)
    for t0, t1, pf, ps in bursts:
        tr.span("burst", t0, t1, track="engine:colocated",
                args={"steps": 4, "n_active": 2, "prefill_tokens": pf,
                      "prefill_steps": ps, "decode_tokens": 8 - pf})
    for t0, t1 in syncs:
        tr.span("sync", t0, t1, track="engine:colocated",
                args={"kind": "drain"})
    return tr


BURSTS = [(0.0, 0.001, 6, 4), (0.102, 0.104, 3, 2), (0.205, 0.206, 0, 0)]
SYNCS = [(0.001, 0.1), (0.104, 0.2)]


def test_prompt_tokens_per_step_over_the_window():
    tr = _ring(BURSTS, SYNCS)
    assert pt.prompt_tokens_per_step(tr, (0.0, 1.0)) == pytest.approx(1.5)
    assert pt.prompt_tokens_per_step(tr, (0.1, 1.0)) == pytest.approx(1.5)
    assert pt.prompt_tokens_per_step(tr, (0.2, 1.0)) is None


def test_host_turnaround_is_burst_end_less_last_sync_end():
    tr = _ring(BURSTS, SYNCS)
    # boundaries: 0.104 - 0.1 and 0.206 - 0.2
    assert pt.host_turnaround_ms(tr, (0.0, 1.0)) == pytest.approx(5.0)
    assert pt.host_turnaround_ms(tr, (0.15, 1.0)) == pytest.approx(6.0)
    # a boundary with no sync between its bursts is skipped
    tr = _ring(BURSTS, SYNCS[:1])
    assert pt.host_turnaround_ms(tr, (0.0, 1.0)) == pytest.approx(4.0)


def test_attention_ms_per_step_traced():
    scopes = {"attention": 0.03, "attention/core": 0.01, "mlp": 0.05}
    assert pt.attention_ms(scopes, 6) == pytest.approx(5.0)
    assert pt.attention_ms(scopes, 0) is None
    assert pt.attention_ms({"mlp": 0.05}, 6) is None


def test_a_ring_that_dropped_events_reads_nothing():
    tr = _ring(BURSTS, SYNCS, capacity=3)
    assert tr.n_dropped > 0
    assert pt.prompt_tokens_per_step(tr, (0.0, 1.0)) is None
    assert pt.host_turnaround_ms(tr, (0.0, 1.0)) is None


EXCERPT = ROOT / "bench/testdata/program_trace_excerpt.json"


def test_recorded_chip_excerpt_reduces_to_known_numbers():
    """An excerpt of a real v5e trace of the chat cell with the program's
    tracer on: a burst's last 10 ms (the layer scan restacking the KV
    arena), the idle gap its drain leaves, and the next burst's first
    10 ms (the hoisted weight casts, then a step's layers)."""
    import json
    d = json.loads(EXCERPT.read_text())
    ops = [Event(DEV, OPS_LINE, pt.scope_of(path), start, dur)
           for _, path, start, dur in d["ops"]]
    spans = [pt.span(n, s, dur, tuple(a.items()))
             for n, s, dur, a in d["spans"]]
    scopes = pt.device_scopes(ops)
    assert scopes == pytest.approx({
        "weight_cast": 0.007211965, "layer_scan": 0.007145256,
        "attention": 0.001310309, "attention/core": 0.001148697,
        "attention/kv_write": 0.000075756, "mlp": 0.000790871,
        "head": 0.000620172, "feed_sample": 2.92e-06,
        pt.UNSCOPED: 2.91e-07}, abs=1e-12)
    # the scopes (without attention's parts) add up to the busy time
    busy = 0.017081784
    assert sum(v for k, v in scopes.items() if "/" not in k) == \
        pytest.approx(busy, abs=1e-12)
    extent = (min(e.start_ns for e in ops),
              max(e.start_ns + e.dur_ns for e in ops))
    idle = pt.idle_by_span(ops, spans, window_ns=extent)
    assert idle == pytest.approx({
        "sync.drain": 0.002780987, "burst": 0.000386448,
        "emit": 0.00026268, "iteration": 0.00025417, "admit": 3.044e-05,
        pt.NO_SPAN: 2.12e-05}, abs=1e-12)
    assert sum(idle.values()) == pytest.approx(
        (extent[1] - extent[0]) * 1e-9 - busy, abs=1e-12)
    # the burst's args as the profiler recorded them
    (burst,) = [dict(s.args) for s in spans if s.name == "burst"]
    assert burst == {"steps": "4", "n_active": "30", "prefill_tokens": "28",
                     "prefill_steps": "4", "decode_tokens": "92",
                     "synced": "0"}
    assert pt.burst_steps(spans) == 4
