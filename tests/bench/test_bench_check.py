"""The check that decides ``correct``: a run driven end to end at a small
size on the CPU (the harness's look for a chip skipped) agrees with the
float32 reference, and the same run with a token altered where it is
produced does not."""
import dataclasses
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import faults, harness, program, reference, spec  # noqa: E402
from bench.weights import Dims, root_key  # noqa: E402
from repro.configs import registry  # noqa: E402

SEED = 2**33 + 5


def _small_cell(name: str):
    """The cell with its model at the registry's smoke size (the
    configuration file's numbers set to it, so the weights and the
    reference follow), small traffic and a short lead-in: what a CPU can
    run in seconds."""
    cell = spec.load_cell(name)
    cfg = dict(cell.config)
    smoke = dataclasses.replace(registry.get(cfg["arch"]).smoke,
                                scan_chunk=16,
                                norm_eps=float(cfg["rms_norm_eps"]))
    if "mup" in cfg:
        # muP scales the embedding up: matrices as wide in effect as at the
        # published width keep what the layers add to the residual stream
        # at its size against it, so the tokens depend on every layer
        widen = (cfg["hidden_size"] / smoke.d_model) ** 0.5
        cfg["init"] = dict(cfg["init"],
                           matrix_std=cfg["init"]["matrix_std"] * widen)
    cfg.update(hidden_size=smoke.d_model, num_hidden_layers=smoke.n_layers,
               num_attention_heads=smoke.n_heads,
               num_key_value_heads=smoke.n_kv_heads, head_dim=smoke.hd,
               intermediate_size=smoke.d_ff, vocab_size=smoke.vocab)
    if "dim_model_base" in cfg:          # muP's logit scale as published
        cfg["dim_model_base"] = smoke.d_model * 256 // 2304
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
    return dataclasses.replace(cell, config=cfg, traffic=mix), smoke


def _run(monkeypatch, name, fault=None):
    cell, smoke = _small_cell(name)
    monkeypatch.setattr(program, "model_config", lambda c: smoke)
    return harness.run_cell(
        cell, seed=SEED, seconds=3.0, trace=False,
        t_start=time.perf_counter(), peaks=spec.load_peaks("TPU v5 lite"),
        devices=jax.devices(), fault=fault)


@pytest.mark.parametrize("cell,metric", [("qwen2_1_5b.chat", "ttft_p90_s"),
                                         ("minicpm_2b.decode",
                                          "output_tok_per_s")])
def test_served_tokens_agree_with_the_reference(monkeypatch, cell, metric):
    """The chat cell, and the decode cell's backlog that counts the
    requests finishing in the window, with minicpm's smoke model (MHA,
    muP's scalars folded into the weights the program gets)."""
    out = _run(monkeypatch, cell)
    c = out["checks"]
    assert c["served_tokens_compared"]["value"] >= 100
    assert c["widest_logit_gap"]["value"] <= 0.05
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert "setup_s" in out["metrics"] and metric in out["metrics"]


@pytest.mark.parametrize("cell", ["qwen2_1_5b.chat", "minicpm_2b.decode"])
def test_an_altered_token_fails_the_check(monkeypatch, cell):
    out = _run(monkeypatch, cell, fault=faults.alter_tokens)
    assert out["checks"]["widest_logit_gap"]["value"] > 0.05
    assert out["correct"] is False


def test_float8_control_fails_the_check():
    """The control, the reference computed with float8 weight matmuls, put
    in the program's place at a size a test run holds (qwen2's widths, ten
    layers, a 32k vocabulary): the tokens it puts first read a widest gap
    above the limit the chat cell states."""
    limit = spec.load_cell("qwen2_1_5b.chat").config["correct"][
        "max_logit_gap"]
    dims = Dims(n_layers=10, d_model=1536, n_heads=12, n_kv_heads=2,
                head_dim=128, d_ff=8960, vocab=32768, qkv_bias=True,
                rope_theta=1e6, norm_eps=1e-6, weight_dtype="bfloat16")
    ref = reference.Reference(dims, root_key(SEED), 128)
    rng = np.random.default_rng(SEED)
    samples = [(rng.integers(0, dims.vocab, 32, dtype=np.int32),
                rng.integers(0, dims.vocab, 96, dtype=np.int32))
               for _ in range(4)]
    _, ctrl = reference.served_gaps(ref, samples, control=True)
    assert ctrl > (limit if limit is not None else 0.0)
