"""The check that decides ``correct``: a run driven end to end at a small
size on the CPU (the harness's look for a chip skipped) agrees with the
float32 reference, and the same run with a token altered where it is
produced does not."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import faults, harness, reference, spec  # noqa: E402
from bench.weights import root_key  # noqa: E402

SEED = 2**33 + 5
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
# every cell, with the first end-to-end metric it reports besides setup_s:
# each configuration of BENCHMARK.json is driven by some cell
CELLS = [(w["name"], next(m["name"] for m in BENCH["end_to_end"]
                          if m["name"] != "setup_s"
                          and w["name"] in m.get("workloads", [w["name"]])))
         for w in BENCH["workloads"]]


def small_cell(cell: spec.Cell) -> spec.Cell:
    """The cell with its model cut to the registry's smoke size by its
    model module (``small``), small traffic and a short lead-in: what a CPU
    can run in seconds."""
    cfg = cell.model.small(cell.config)
    cfg["serving"] = dict(cfg["serving"], n_slots=4, max_seq=160,
                          total_blocks=None)
    cfg["correct"] = dict(cfg["correct"], max_logit_gap=0.05)
    mix = dict(cell.traffic, lead_in_s=1.0)
    mix["prompt"] = dict(mix["prompt"], median=24)
    mix["output"] = dict(mix["output"], median=12)
    mix["keep"] = {"prompt": [4, 64], "output": [8, 64], "total_max": 128}
    if mix["arrivals"]["kind"] == "poisson":
        mix["arrivals"] = dict(mix["arrivals"], rate_per_s=6.0)
        mix["drain_cap_s"] = 30.0
    else:
        mix["arrivals"] = dict(mix["arrivals"], count=40)
    return dataclasses.replace(cell, config=cfg, traffic=mix)


def run_small(cell: spec.Cell, fault=None):
    return harness.run_cell(
        small_cell(cell), seed=SEED, seconds=3.0, trace=False,
        t_start=time.perf_counter(), peaks=spec.load_peaks("TPU v5 lite"),
        devices=jax.devices(), fault=fault)


@pytest.mark.parametrize("cell,metric", CELLS)
def test_served_tokens_agree_with_the_reference(cell, metric):
    """Every cell at its model's smoke size: the chat cell, and the decode
    cell's backlog that counts the requests finishing in the window, with
    minicpm's smoke model (MHA, muP's scalars folded into the weights the
    program gets)."""
    out = run_small(spec.load_cell(cell))
    c = out["checks"]
    assert c["served_tokens_compared"]["value"] >= 100
    assert c["widest_logit_gap"]["value"] <= 0.05
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert "setup_s" in out["metrics"] and metric in out["metrics"]


@pytest.mark.parametrize("cell", [c for c, _ in CELLS])
def test_an_altered_token_fails_the_check(cell):
    out = run_small(spec.load_cell(cell), fault=faults.alter_tokens)
    assert out["checks"]["widest_logit_gap"]["value"] > 0.05
    assert out["correct"] is False


def test_float8_control_fails_the_check():
    """The control, the reference computed with float8 weight matmuls, put
    in the program's place at a size a test run holds (qwen2's widths, ten
    layers, a 32k vocabulary): the tokens it puts first read a widest gap
    above the limit the chat cell states."""
    cell = spec.load_cell("qwen2_1_5b.chat")
    limit = cell.config["correct"]["max_logit_gap"]
    config = dict(cell.config, num_hidden_layers=10, vocab_size=32768)
    ref = cell.model.Reference(config, root_key(SEED), 128)
    rng = np.random.default_rng(SEED)
    samples = [(rng.integers(0, config["vocab_size"], 32, dtype=np.int32),
                rng.integers(0, config["vocab_size"], 96, dtype=np.int32))
               for _ in range(4)]
    _, ctrl = reference.served_gaps(ref, samples, control=True)
    assert ctrl > (limit if limit is not None else 0.0)


# a model module loaded, and its reference run, where nothing of the program
# can be imported
WITHOUT_PROGRAM = """
import json, sys


class NoProgram:
    def find_spec(self, name, path=None, target=None):
        if name.split(".")[0] == "repro":
            raise ImportError(name + " is the program")


sys.meta_path.insert(0, NoProgram())
sys.path[:0] = [sys.argv[1]]
import numpy as np
from bench import spec, weights
job = json.loads(sys.argv[2])
ref = spec.load_model(job["model"]).Reference(
    job["config"], weights.root_key(job["seed"]), job["seq_len"])
(gaps, _), = ref.gaps([np.asarray(job["seq"], np.int32)],
                      [np.asarray(job["next"], np.int32)])
assert not [m for m in sys.modules if m.split(".")[0] == "repro"]
print(json.dumps(gaps.tolist()))
"""


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_reference_runs_without_the_program(name):
    """Each configuration's model module loads, and its reference gives the
    same gaps, in a process where ``repro`` cannot be imported."""
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    config = json.loads((ROOT / entry["file"]).read_text())
    model = spec.load_model(config["model"])
    rng = np.random.default_rng(SEED)
    job = {"model": config["model"], "config": model.small(config),
           "seed": SEED, "seq_len": 16,
           "seq": rng.integers(0, 512, 12).tolist(),
           "next": rng.integers(0, 512, 12).tolist()}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", WITHOUT_PROGRAM, str(ROOT), json.dumps(job)],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    ref = model.Reference(job["config"], root_key(SEED), job["seq_len"])
    (want, _), = ref.gaps([np.asarray(job["seq"], np.int32)],
                          [np.asarray(job["next"], np.int32)])
    np.testing.assert_array_equal(
        np.asarray(json.loads(out.stdout.splitlines()[-1]), np.float32), want)


# a model family new to the harness, as a later configuration would bring
# it: here the dense decoder's numbers under a module of its own name, each
# call of the interface recorded
NEW_MODEL = '''"""A test double of a new model family."""
from pathlib import Path

from bench import spec

_base = spec.load_module(Path(__file__).with_name("dense_decoder.py"),
                         "bench_model_new_family_base")
CALLS = []


def _recorded(name):
    def call(*args, **kw):
        CALLS.append(name)
        return getattr(_base, name)(*args, **kw)
    return call


program_config = _recorded("program_config")
program_params = _recorded("program_params")
Reference = _recorded("Reference")
step_counts = _recorded("step_counts")
small = _base.small
'''


def test_new_model_family_needs_no_edit(tmp_path):
    """A configuration naming a new model module, added as new files and
    entries only, resolves and runs end to end at its ``small()`` size
    through that module's interface; every file under ``bench/`` that was
    there is unchanged."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    (tmp_path / "bench/models/new_family.py").write_text(NEW_MODEL)
    cfg = json.loads((ROOT / "bench/configs/qwen2_1_5b.json").read_text())
    cfg["model"] = "new_family"
    (tmp_path / "bench/configs/new_family_1b.json").write_text(
        json.dumps(cfg))
    bench_json = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench_json["configs"].append(
        {"name": "new_family_1b", "source": "https://example.org/cfg",
         "file": "bench/configs/new_family_1b.json", "reduced": [],
         "why": "t"})
    bench_json["workloads"].append(
        {"name": "new_family_1b.chat", "config": "new_family_1b",
         "traffic": "chat", "chips": 1, "why": "t"})
    for m in bench_json["end_to_end"]:
        if m["name"] == "ttft_p90_s":
            m["workloads"].append("new_family_1b.chat")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench_json))

    cell = spec.load_cell("new_family_1b.chat", root=tmp_path)
    assert cell.model.__file__ == str(tmp_path / "bench/models/new_family.py")
    out = run_small(cell)
    assert set(cell.model.CALLS) == {"program_config", "program_params",
                                     "Reference", "step_counts"}
    assert out["correct"] is True
    assert out["checks"]["served_tokens_compared"]["value"] >= 100
    assert "ttft_p90_s" in out["metrics"]
    after = {p: p.read_bytes()
             for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    assert all(after[p] == b for p, b in before.items())
