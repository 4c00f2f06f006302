"""Tiny versions of the benchmark's cells for CPU tests: every published
width cut down, the kinds of layers and the optimizer kept."""
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Qwen3-8B's published config.json (huggingface.co/Qwen/Qwen3-8B), in the
# configuration files' keys: grouped-query attention, qk-norm and SwiGLU,
# the layer kinds the reference covers beside gpt2-paper's
QWEN3_8B = {
    "name": "qwen3-8b", "arch": "qwen3-14b", "reference": "transformer",
    "num_hidden_layers": 36,
    "hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 8,
    "head_dim": 128, "intermediate_size": 12288, "vocab_size": 151936,
    "hidden_act": "silu", "attention_bias": False, "qk_norm": True,
    "rope_theta": 1000000, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16"}


def read(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def config(name: str) -> dict:
    """16 heads, so the biases of gpt2-paper stay Muon matrices and
    Qwen3's grouped heads (4 per key/value head) group as at full size."""
    c = QWEN3_8B if name == "qwen3-8b" else read("configs", name + ".json")
    kv = 16 if c["num_key_value_heads"] == c["num_attention_heads"] else 4
    return dict(c, num_hidden_layers=2, hidden_size=64,
                num_attention_heads=16, num_key_value_heads=kv, head_dim=16,
                intermediate_size=128, vocab_size=256)


def cell(name: str) -> dict:
    """A resolved cell (as ``harness.resolve`` gives it) at tiny size,
    judged by the gpt2-paper cell's limits."""
    c = config(name)
    return {"workload": {"name": name + ".muon-8k", "chips": 1},
            "config": c,
            "reference": os.path.join(BENCH, "reference",
                                      c["reference"] + ".py"),
            "traffic": dict(read("traffic", "muon-8k.json"), seq=32,
                            batch=4),
            "limits": read("limits", "gpt2-paper.muon-8k.json"),
            "per_layer": [], "readers": {}}
