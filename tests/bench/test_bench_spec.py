"""BENCHMARK.json and the files it names: found by name, and a new cell,
traffic mix, configuration or metric needs no edit to an existing file."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/")
    names = [c["name"] for c in BENCH["configs"]]
    cells = [w["name"] for w in BENCH["workloads"]]
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    for n in names + cells + metrics:
        assert NAME.match(n), n
    assert len(set(names)) == len(names)
    assert len(set(cells)) == len(cells)
    assert len(set(metrics)) == len(metrics)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == set(names)
    for c in BENCH["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["reduced"] == c["reduced"]
        assert c["source"].startswith("https://")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        # every cell it lists reports the end-to-end metric it moves
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", cells)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = spec.load_cell(cell)
    assert c.end_to_end and c.per_layer
    assert "setup_s" in [m.name for m in c.end_to_end]
    assert callable(c.generator.generate)


def test_peaks_table_is_keyed_by_device_kind():
    assert spec.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.load_peaks("TPU v9 imaginary")


def _snapshot(root: Path) -> dict:
    return {p: p.read_bytes() for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_cell_traffic_config_metric_need_no_edit(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench_json = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = _snapshot(tmp_path / "bench")
    # new files only
    cfg = json.loads((ROOT / "bench/configs/qwen2_1_5b.json").read_text())
    (tmp_path / "bench/configs/qwen_copy.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic/chat.json").read_text())
    mix["arrivals"]["rate_per_s"] = 0.5
    (tmp_path / "bench/traffic/slow_chat.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/requests_counted.py").write_text(
        "def read(run):\n    return len(run.counted)\n")
    # new entries only
    bench_json["configs"].append(
        {"name": "qwen_copy", "source": "https://example.org/cfg",
         "file": "bench/configs/qwen_copy.json", "reduced": [], "why": "t"})
    bench_json["workloads"].append(
        {"name": "qwen_copy.slow_chat", "config": "qwen_copy",
         "traffic": "slow_chat", "chips": 1, "why": "t"})
    bench_json["per_layer"].append(
        {"name": "requests_counted", "unit": "requests", "better": "higher",
         "source": "program_counter", "layer": "driver and batcher",
         "moves": "output_tok_per_s", "workloads": ["qwen_copy.slow_chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench_json))
    cell = spec.load_cell("qwen_copy.slow_chat", root=tmp_path)
    assert cell.traffic["arrivals"]["rate_per_s"] == 0.5
    assert cell.config["arch"] == "qwen2_1_5b"
    reader = {m.name: m for m in cell.per_layer}["requests_counted"]

    class FakeRun:
        counted = [1, 2, 3]
    assert reader.read(FakeRun()) == 3
    # every file that was there is unchanged
    after = _snapshot(tmp_path / "bench")
    assert all(after[p] == b for p, b in before.items())


def _run(root: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "bench/run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", str(2**33 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu_backend():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_needs_more_than_the_benchmark_files(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's own
    files cannot run a cell: the system under test is missing."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_output_rate_spans_whole_iterations():
    """Tokens arrive at iteration ends: the rate counts what the
    iterations after the window's first end delivered, over the time to
    its last end, so where the window falls between ends does not matter."""
    reader = {m.name: m for m in spec.load_cell(
        "minicpm_2b.decode").end_to_end}["output_tok_per_s"]

    class FakeRun:
        window = (10.0, 20.0)
        iteration_ends = [9.0, 11.0, 13.5, 16.0, 18.5, 21.0]
        # 100 tokens at each end, the first two deltas just before it
        deliveries = [(t - 0.01, 60) for t in iteration_ends] + [
            (t, 40) for t in iteration_ends]

        def in_window(self, t):
            return self.window[0] <= t < self.window[1]
    assert reader.read(FakeRun()) == pytest.approx(300 / 7.5)
    FakeRun.iteration_ends = [11.0]
    assert reader.read(FakeRun()) is None
