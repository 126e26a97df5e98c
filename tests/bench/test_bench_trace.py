"""The reduction from trace events to busy time, idle gaps named by the
host span over them, and the top device operations."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(plane, line, name, start, dur):
    return trace.Event(plane, line, name, float(start), float(dur))


EVENTS = [
    _ev(DEV, trace.OPS_LINE, "fusion.1", 0, 10),       # 0-10 busy
    _ev(DEV, trace.OPS_LINE, "fusion.2", 2, 6),        # nested in fusion.1
    _ev(DEV, trace.OPS_LINE, "fusion.1", 30, 10),      # 30-40 busy
    _ev(DEV, trace.OPS_LINE, "copy.3", 100, 50),       # 100-150 busy
    _ev(DEV, trace.MODULES_LINE, "jit_burst(7)", 0, 40),
    _ev(DEV, trace.MODULES_LINE, "jit_scatter(2)", 100, 50),
    _ev(HOST, "python", "dispatch", 18, 10),           # covers 18-28
    _ev(HOST, "python", "scan", 28, 80),               # covers 28-108
]


def test_busy_time_is_the_union_of_device_operations():
    s = trace.reduce_trace(EVENTS)
    assert s.busy_s == pytest.approx((10 + 10 + 50) * 1e-9)
    assert s.n_devices == 1


def test_idle_gaps_are_named_by_the_host_span_over_most_of_them():
    s = trace.reduce_trace(EVENTS)
    # gaps 10-30 (dispatch 10 ns, scan 2 ns) and 40-100 (scan)
    assert s.idle_gaps == [("scan", pytest.approx(60e-9)),
                           ("dispatch", pytest.approx(20e-9))]


def test_top_operations_and_module_time():
    s = trace.reduce_trace(EVENTS, top=2)
    # self time: fusion.1 holds fusion.2 for 6 of its first 10 ns
    assert s.device_ops == [("copy.3", pytest.approx(50e-9)),
                            ("fusion.1", pytest.approx(14e-9))]
    assert trace.module_time(s, "jit_burst") == pytest.approx(40e-9)
    assert trace.module_time(s, "jit_nothing") is None


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_trace([e for e in EVENTS if e.plane == HOST])


EXCERPT = ROOT / "bench/testdata/trace_excerpt.json"


def test_recorded_chip_excerpt_reduces_to_known_numbers():
    """An excerpt of a real v5e trace: a burst's last 6 ms, the idle gap
    the host's completion scan leaves, and the next burst's first 6 ms."""
    import json
    rows = json.loads(EXCERPT.read_text())["events"]
    evs = [trace.Event(*r) for r in rows]
    s = trace.reduce_trace(evs, top=3)
    assert s.busy_s == pytest.approx(0.009475505, abs=1e-12)
    assert s.device_ops == [
        ("convert.42", pytest.approx(0.00351082, abs=1e-12)),
        ("copy.83", pytest.approx(0.002106688, abs=1e-12)),
        ("copy.84", pytest.approx(0.002106151, abs=1e-12))]
    assert s.idle_gaps[0] == ("scan", pytest.approx(0.002332477, abs=1e-12))
    # operations nest (a while holds its body), so self times add up to
    # the busy time
    ops = [e for e in evs if e.line == trace.OPS_LINE]
    assert sum(trace._self_times(ops).values()) * 1e-9 == pytest.approx(
        s.busy_s, abs=1e-12)


def test_events_reads_host_spans_from_a_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    x = jnp.ones((8, 8))
    (x @ x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("dispatch"):
        (x @ x).block_until_ready()
    jax.profiler.stop_trace()
    evs = trace.events(trace.find_xplane(str(tmp_path)))
    assert [e.name for e in evs if e.line != trace.OPS_LINE] == ["dispatch"]


def test_op_names_are_the_instruction_names():
    assert trace.op_name("%fusion.12 = bf16[2]{0} fusion(%a)") == "fusion.12"
    assert trace.op_name("jit_burst(123)") == "jit_burst(123)"
