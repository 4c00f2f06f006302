"""The operation and byte counts against hand counts."""
import pytest

from chipbench import flops
from chipbench.reference import transformer as ref_model
from chipbench.tests import tiny


def test_one_polar_iteration_on_the_qwen_mlp():
    # Gram 2 m n^2 + apply 2 m n^2 + one Horner product 2 n^3
    assert flops.polar_flops((17408, 5120), iters=1) == \
        4 * 17408 * 5120 ** 2 + 2 * 5120 ** 3 == 2_093_796_556_800
    assert flops.polar_flops((5120, 17408), iters=3) == \
        3 * flops.polar_flops((17408, 5120), iters=1)


def test_polar_bytes_count_iterate_and_residual():
    assert flops.polar_hbm_bytes((4096, 1024), "float32", iters=2) == \
        2 * (2 * 4096 * 1024 + 2 * 1024 * 1024) * 4
    assert flops.polar_hbm_bytes((1024, 4096), "bfloat16", iters=1) == \
        (2 * 4096 * 1024 + 2 * 1024 * 1024) * 2


@pytest.mark.parametrize("name,want", [
    # gpt2-paper: 10 x (4 d^2 + 2 d f) + d V matrix parameters, plus
    # 12 L H hd S attention FLOPs per token, over 8192 tokens
    ("gpt2-paper", lambda L: 8192 * (
        6 * (L * (4 * 1024 ** 2 + 2 * 1024 * 4096) + 1024 * 50257)
        + 12 * L * 16 * 64 * 1024)),
    # qwen3-8b: per layer q, o 4096 x 4096; k, v 4096 x 1024; 3 MLP
    # matrices 4096 x 12288; the head 4096 x 151936
    ("qwen3-8b", lambda L: 8192 * (
        6 * (L * 192_937_984 + 4096 * 151936) + 12 * L * 32 * 128 * 1024)),
])
def test_train_step_flops(name, want):
    c = (tiny.QWEN3_8B if name == "qwen3-8b"
         else tiny.read("configs", name + ".json"))
    assert ref_model.train_step_flops(c, 1024, 8) == want(
        c["num_hidden_layers"])


def test_least_time_takes_the_binding_roof():
    peak_f, peak_b = 197e12, 819e9
    secs, f, b, bound = flops.polar_least_seconds(
        [(3, (17408, 5120))], "float32", iters=3, degree=2,
        peak_flops=peak_f, peak_bytes=peak_b)
    assert bound == "compute" and secs == pytest.approx(f / peak_f)
    # a thin bucket streams more bytes per FLOP than the chip can feed
    secs, f, b, bound = flops.polar_least_seconds(
        [(30, (64, 16))], "float32", iters=3, degree=2,
        peak_flops=peak_f, peak_bytes=peak_b)
    assert bound == "memory" and secs == pytest.approx(b / peak_b)
