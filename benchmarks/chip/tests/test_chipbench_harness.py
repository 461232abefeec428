"""The harness's yardstick and plumbing on the CPU: peaks, operation and
byte counts against hand-worked shapes, discovery of a cell's files by
name, and the refusal to run anywhere but on a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from chipbench_testing import BENCH
from chipbench import flops, harness, peaks


def test_peaks_are_keyed_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="cpu"):
        peaks.peaks("cpu")


def test_flash_attention_counts_by_hand():
    # B=1, S=4, H=2, KVH=1, Dh=8: 2 heads x 10 causal pairs; 6 matmuls
    ops, nbytes = flops.flash_attention_train(1, 4, 2, 1, 8)
    assert ops == 6 * 2 * 8 * 20
    # q = o = 128 B, k = v = 64 B, lse 32 B: fwd 416 B, bwd 800 B
    assert nbytes == 416 + 800


def test_ssd_counts_by_hand():
    # B=1, S=8, H=2, P=4, G=1, N=2, chunk 4: 2 chunks of 10 causal pairs
    ops, nbytes = flops.ssd_train(1, 8, 2, 4, 1, 2, 4, 2, 2)
    assert ops == 3 * 2 * (2 * 2 * 10 + 2 * 2 * 4 * 10 + 2 * 2 * 4 * 4 * 2)
    assert nbytes == 704 + 960


def test_model_counts_by_hand():
    model = harness.load_module(BENCH / "models" / "internlm2.py")
    cfg = {"hidden_size": 4, "num_attention_heads": 2,
           "num_key_value_heads": 1, "head_dim": 2, "intermediate_size": 8,
           "vocab_size": 10, "num_hidden_layers": 1}
    # layer matmuls 144 MACs, causal attention 2*2*2*2*1.5, head 40 MACs
    assert model.fwd_flops_per_token(cfg, 2) == 2 * 144 + 24 + 2 * 40
    assert model.train_flops_per_token(cfg, 2) == 3 * 392
    m2 = harness.load_module(BENCH / "models" / "mamba2.py")
    cfg = {"d_model": 4, "n_layer": 1, "vocab_size": 9,
           "pad_vocab_size_multiple": 4,
           "ssm_cfg": {"d_state": 2, "d_conv": 2, "expand": 2, "headdim": 4,
                       "ngroups": 1, "chunk_size": 2}}
    # d_in 8, 2 heads, conv_dim 12, vocab padded to 12
    proj = 2 * 4 * (16 + 4 + 2) + 2 * 8 * 4
    ssd = 2 * 2 * 1.5 + 2 * 2 * 4 * 1.5 + 4 * 2 * 4 * 2
    assert m2.fwd_flops_per_token(cfg, 4) == proj + 2 * 2 * 12 + ssd + 96


def test_roofline_share():
    assert flops.roofline(197e12, 0, 2.0, 197e12, 819e9) == (50.0, "compute")
    assert flops.roofline(0, 819e9, 4.0, 197e12, 819e9) == (25.0, "memory")


def test_harness_finds_a_cell_it_has_no_code_for(tmp_path):
    """A new cell, job kind and per-layer metric are new files only."""
    for d in ("configs", "traffic", "limits", "jobs", "metrics"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "toy.json").write_text(
        json.dumps({"model_type": "toy"}))
    (tmp_path / "traffic" / "toy_mix.json").write_text(
        json.dumps({"job": "toy_job", "n": 3}))
    (tmp_path / "limits" / "toy.cell.json").write_text(
        json.dumps({"gap": 1.0}))
    (tmp_path / "jobs" / "toy_job.py").write_text(
        "def run(run):\n"
        "    n = run.traffic['n']\n"
        "    return {'metrics': {'toy_rate': n}, 'facts': {'n': n},\n"
        "            'checks': {'gap': {'value': 0.5,\n"
        "                               'limit': run.cell.limits['gap']}}}\n")
    (tmp_path / "metrics" / "toy.share.py").write_text(
        "def read(ctx):\n    return 10.0 * ctx['facts']['n']\n")
    spec = {"workloads": [{"name": "toy.cell", "config": "toy",
                           "traffic": "toy_mix", "chips": 1, "why": "t"}],
            "end_to_end": [{"name": "toy_rate", "unit": "1/s"},
                           {"name": "other", "unit": "s",
                            "workloads": ["elsewhere"]}],
            "per_layer": [{"name": "toy.share", "unit": "%",
                           "moves": "toy_rate"},
                          {"name": "not.here", "unit": "%", "moves": "other"}]}
    (tmp_path / "spec.json").write_text(json.dumps(spec))
    cell = harness.find_cell("toy.cell", tmp_path / "spec.json", tmp_path)
    assert [m["name"] for m in cell.end_to_end] == ["toy_rate"]
    assert [m["name"] for m in cell.per_layer] == ["toy.share"]
    run = harness.Run(cell, 1, 1.0, False, [], 0.0)
    out = cell.job().run(run)
    assert harness.correct(out["checks"])
    assert cell.reader("toy.share").read({"facts": out["facts"]}) == 30.0


def test_run_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "internlm2-1.8b.train.phase1", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert p.returncode != 0
    assert "cpu" in p.stderr and p.stdout.strip() == ""


def test_every_cell_has_its_files():
    import re
    spec = harness.load_json(BENCH.parents[1] / "BENCHMARK.json")
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    metrics = spec["end_to_end"] + spec["per_layer"]
    assert any(m["name"] == "setup_s" for m in spec["end_to_end"])
    for m in metrics:
        assert name.match(m["name"]) and m["better"] in ("lower", "higher")
    for m in spec["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        cfg = harness.load_json(BENCH.parents[1] / c["file"])
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        harness.program_config(cfg)       # the program's widths agree
    for w in spec["workloads"]:
        assert name.match(w["name"]) and w["config"] in configs
        cell = harness.find_cell(w["name"])
        assert (BENCH / "jobs" / f"{cell.traffic['job']}.py").exists()
        assert (BENCH / "reference"
                / f"{cell.config['model_type']}.py").exists()
        assert cell.limits and cell.per_layer
        assert any(m["name"] != "setup_s" for m in cell.end_to_end)
