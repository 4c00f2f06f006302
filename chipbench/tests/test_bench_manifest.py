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

import jax
import numpy as np
import pytest

from chipbench import harness, program
from chipbench.reference import transformer as ref_model
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
        assert ref_model.layer_matrix_params(c) == 192_937_984
    else:
        assert c["reduced"] == {}
        # the paper's 229M parameters: matrices, biases, embedding, norms
        total = ref_model.matmul_params(c) + 50257 * 1024 + 10 * 3 * 1024 \
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


TOY_FAMILY = '''"""A family the program does not have: one dense matrix, then a
stack of routed experts [L, E, d, f] per layer, each token through k."""
import jax
import jax.numpy as jnp

from chipbench.reference.controls import identity


def param_shapes(c):
    L, E = c["num_hidden_layers"], c["n_routed_experts"]
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    return {"embed": (c["vocab_size"], d), "dense": {"w": (1, d, d)},
            "layers": {"experts": {"w_up": (L, E, d, f),
                                   "w_down": (L, E, f, d)}}}


def init(key, c):
    shapes, treedef = jax.tree.flatten(
        param_shapes(c), is_leaf=lambda s: isinstance(s, tuple))
    return jax.tree.unflatten(treedef, [
        jax.random.normal(jax.random.fold_in(key, i), s) * s[-2] ** -0.5
        for i, s in enumerate(shapes)])


def loss_and_grad(params, tokens, c, q8=identity, block_rows=0):
    def loss(p):
        x = p["embed"][tokens]
        x = x + jnp.tanh(q8(x) @ q8(p["dense"]["w"][0]))
        ex = p["layers"]["experts"]
        for up, down in zip(ex["w_up"], ex["w_down"]):
            x = x + sum(jnp.tanh(q8(x) @ q8(u)) @ q8(w)
                        for u, w in zip(up, down)) / len(up)
        return jnp.mean(jnp.square(x))
    return jax.value_and_grad(loss)(params)


def train_step_flops(c, seq, batch):
    d, f, k = (c["hidden_size"], c["moe_intermediate_size"],
               c["num_experts_per_tok"])
    return 6 * seq * batch * (d * d + c["num_hidden_layers"] * k * 2 * d * f)


def muon_views(c):
    return {"dense/w": (1, (0,)), "layers/experts/w_up": (2, (0,)),
            "layers/experts/w_down": (2, (1,))}
'''


def _digest(root):
    return {p: p.read_bytes() for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_new_model_family_needs_only_new_files(tmp_path):
    """A configuration names its own reference module, whose Muon leaves
    include an expert stack: ``drivers/train.py``'s reference side takes
    the views, the layer draw, the chain check and the step FLOPs from
    it, and no file of the benchmark changes."""
    from chipbench.reference import muon as ref_muon

    root = tmp_path / "repo"
    shutil.copytree(harness.HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("_jax_cache", "tests",
                                                  "__pycache__"))
    before = _digest(root)
    bench = root / "chipbench"
    (bench / "reference" / "toy_moe.py").write_text(TOY_FAMILY)
    c = {"name": "toy-moe", "arch": "toy", "reference": "toy_moe",
         "num_hidden_layers": 3, "n_routed_experts": 4, "hidden_size": 32,
         "moe_intermediate_size": 16, "num_experts_per_tok": 2,
         "vocab_size": 64}
    (bench / "configs" / "toy-moe.json").write_text(json.dumps(c))
    (bench / "limits" / "toy-moe.muon-8k.json").write_text(json.dumps(
        tiny.read("limits", "gpt2-paper.muon-8k.json")))
    man = json.loads(json.dumps(MANIFEST))
    man["configs"].append({"name": "toy-moe", "source": "x",
                           "file": "chipbench/configs/toy-moe.json",
                           "reduced": [], "why": "a test"})
    man["workloads"].append({"name": "toy-moe.muon-8k", "config": "toy-moe",
                             "traffic": "muon-8k", "chips": 1,
                             "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    after = _digest(root)
    assert all(after[p] == b for p, b in before.items())

    cell = harness.resolve("toy-moe.muon-8k", str(root / "BENCHMARK.json"),
                           str(root))
    assert cell["reference"] == str(bench / "reference" / "toy_moe.py")
    ref = harness.load_module(cell["reference"])
    driver = harness.load_module(cell["driver"])
    c = cell["config"]
    shapes = ref_muon.leaf_paths(ref.param_shapes(c))
    views = ref_muon.muon_leaves(ref.param_shapes(c), ref.muon_views(c))
    assert set(views) == {"dense/w", "layers/experts/w_up",
                          "layers/experts/w_down"}
    assert ref_muon.matrix_dims(views["layers/experts/w_down"],
                                shapes["layers/experts/w_down"]) == (32, 16)
    key = jax.random.PRNGKey(2 ** 31 + 21)
    layers = driver.sampled_layers(key, {p: shapes[p] for p in views})
    driver.check_layers(layers, shapes)
    assert layers["dense/w"] == 0 and 0 <= layers["layers/experts/w_up"] < 3

    # one reference step, read by the chain check of drivers/train.py: the
    # reference against itself reads the float32 rounding of the parameters
    h = cell["traffic"]["optimizer"]
    params = ref.init(key, c)
    tokens = jax.random.randint(key, (4, 8), 0, c["vocab_size"])
    _, grads = ref.loss_and_grad(params, tokens, c)
    with jax.default_matmul_precision("highest"):
        new, _, used = ref_muon.update(
            grads, ref_muon.init_state(params, views), params, h, views)
    samples = {"p0": driver.pick_layer(params, layers),
               "p1": driver.pick_layer(new, layers),
               "g": driver.pick_layer(used, layers)}
    assert samples["p1"]["layers/experts/w_up"].shape == (1, 4, 32, 16)
    gaps = driver.chain_gaps(samples, views, h)
    assert set(gaps) == set(views) and max(gaps.values()) < 1e-4

    # the chain on a stack is the chain on each expert's own matrix
    u = jax.random.normal(key, shapes["layers/experts/w_down"])
    with jax.default_matmul_precision("highest"):
        stack = ref_muon.direction(views["layers/experts/w_down"], u,
                                   h["prism"])
        for layer in range(3):
            for e in range(4):
                one = ref_muon.polar(u[layer, e].T, h["prism"]).T \
                    * math.sqrt(32 / 16)
                np.testing.assert_allclose(stack[layer, e], one, rtol=1e-5,
                                           atol=1e-6)
    assert ref.train_step_flops(c, 1024, 8) == 6 * 8192 * (
        32 * 32 + 3 * 2 * 2 * 32 * 16)
    # the program has no such family: its translation refuses the file
    with pytest.raises(harness.CellError, match="toy_moe"):
        program.model_config(c)


def test_a_published_config_is_translated_whole():
    """Every shape key of a configuration file reaches the program, and
    a key or a value the program does not take raises instead of being
    dropped."""
    from repro.configs import get_config

    c = tiny.read("configs", "gpt2-paper.json")
    assert program.model_config(c) == get_config("gpt2-paper").replace(
        name="gpt2-paper", d_model=1024, d_ff=4096, num_heads=16,
        num_kv_heads=16, head_dim=64, num_layers=10, vocab_size=50257,
        rope_theta=10000.0, norm_eps=1e-06, tie_embeddings=False,
        qk_norm=False, qkv_bias=True, mlp_act="gelu", dtype="bfloat16",
        emb_dtype="bfloat16")
    assert program.model_config(dict(c, deployment="x", assumed={})) == \
        program.model_config(c)
    with pytest.raises(ValueError, match="n_routed_experts"):
        program.model_config(dict(c, n_routed_experts=64))
    with pytest.raises(KeyError, match="relu"):
        program.model_config(dict(c, hidden_act="relu"))


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
