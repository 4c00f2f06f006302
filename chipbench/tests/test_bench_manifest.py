"""BENCHMARK.json and the files it names: every cell resolves by name,
names and units keep to their characters, and a new configuration,
traffic mix and metric need new files and entries only."""
import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import flops, harness, program
from chipbench.tests import tiny

MANIFEST = json.load(open(harness.MANIFEST))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_every_workload_resolves_to_its_files(workload):
    cell = harness.resolve(workload)
    assert os.path.isfile(cell["driver"])
    assert set(cell["limits"]) == {"loss_gap", "grad_gap", "update_gap",
                                   "chain_gap"}
    assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
    assert cell["per_layer"], "every cell reports a per-layer metric"
    for name, path in cell["readers"].items():
        assert callable(harness.load_module(path).read), name


def test_names_units_and_keys_keep_to_the_contract():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in MANIFEST["configs"]] + CELLS
             + [m["name"] for m in MANIFEST["end_to_end"]
                + MANIFEST["per_layer"]])
    assert len(names) == len(set(names))
    for w in MANIFEST["workloads"]:
        names += [w["config"], w["traffic"]]
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for c in MANIFEST["configs"]:
        names += c["reduced"]
        assert c["file"].startswith("chipbench/")
    for n in names:
        assert NAME.match(n), n
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        assert set(m["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("name", ["gpt2-paper", "qwen3-8b"])
def test_config_files_give_the_published_sizes(name):
    c = (tiny.QWEN3_8B if name == "qwen3-8b"
         else tiny.read("configs", name + ".json"))
    cfg = program.model_config(c)
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads) == (
        c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"])
    if name == "qwen3-8b":
        # q, o 4096 x 4096; k, v 4096 x 1024; 3 MLP matrices 4096 x 12288
        assert flops.layer_matrix_params(c) == 192_937_984
    else:
        assert c["reduced"] == {}
        # the paper's 229M parameters: matrices, biases, embedding, norms
        total = flops.matmul_params(c) + 50257 * 1024 + 10 * 3 * 1024 \
            + 21 * 1024
        assert total == 228_807_680


def test_a_new_cell_needs_only_new_files(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(harness.HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("_jax_cache", "tests"))
    man = json.loads(json.dumps(MANIFEST))
    bench = root / "chipbench"
    cfg = dict(tiny.read("configs", "gpt2-paper.json"), name="gpt2-wide")
    (bench / "configs" / "gpt2-wide.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "muon-32k.json").write_text(json.dumps(
        dict(tiny.read("traffic", "muon-8k.json"), batch=32)))
    (bench / "limits" / "gpt2-wide.muon-32k.json").write_text(json.dumps(
        tiny.read("limits", "gpt2-paper.muon-8k.json")))
    (bench / "metrics" / "tokens_per_step.py").write_text(
        "def read(ctx):\n    return ctx.get('tokens')\n")
    man["configs"].append({"name": "gpt2-wide", "source": "x",
                           "file": "chipbench/configs/gpt2-wide.json",
                           "reduced": [], "why": "a test"})
    man["workloads"].append({"name": "gpt2-wide.muon-32k",
                             "config": "gpt2-wide", "traffic": "muon-32k",
                             "chips": 1, "why": "a test"})
    man["per_layer"].append({"name": "tokens_per_step", "unit": "tokens",
                             "better": "higher", "source": "host_clock",
                             "layer": "trainer host loop",
                             "moves": "train_tokens_per_s",
                             "workloads": ["gpt2-wide.muon-32k"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    cell = harness.resolve("gpt2-wide.muon-32k", str(root / "BENCHMARK.json"),
                           str(root))
    assert cell["traffic"]["batch"] == 32
    assert cell["config"]["name"] == "gpt2-wide"
    assert [m["name"] for m in cell["per_layer"]] == ["tokens_per_step"]
    reader = harness.load_module(cell["readers"]["tokens_per_step"])
    assert reader.read({"tokens": 32768}) == 32768
    assert cell["driver"].endswith("chipbench/drivers/train.py")


def test_unknown_device_kind_raises():
    with pytest.raises(harness.CellError, match="no peaks"):
        harness.peaks("TPU v99")
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_refuses_to_measure_on_cpu():
    p = _run(harness.ROOT, "--workload", CELLS[0], "--seed",
             str(2 ** 31 + 3), "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_fails_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's own
    files has no system to measure."""
    shutil.copytree(harness.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("_jax_cache"))
    shutil.copy(harness.MANIFEST, tmp_path / "BENCHMARK.json")
    p = _run(tmp_path, "--workload", CELLS[0], "--seed", "5", "--seconds",
             "1")
    assert p.returncode != 0 and not p.stdout.strip()


def test_run_seconds_fits_a_full_check():
    rs = MANIFEST["run_seconds"]
    assert 1 <= rs <= 51 and rs == math.floor(rs)
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
