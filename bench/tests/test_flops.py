"""Operation and byte counts against hand counts at a small size."""
import pytest

from bench import flops
from bench.model import Dims

D = Dims(layers=2, d_model=8, heads=4, kv_heads=2, head_dim=2, d_ff=16,
         vocab=32, rope_theta=1e4, rotary_dims=2, norm_eps=1e-6,
         dtype="bfloat16")


def test_parameters():
    # q 8x4x2, k and v 8x2x2 each, o 4x2x8, gate/up/down 8x16 each
    layer = 64 + 32 + 32 + 64 + 3 * 128
    assert flops.layer_matmul_params(D) == layer
    assert flops.matmul_params(D) == 2 * layer + 32 * 8
    # plus two norm scales per layer, the embedding and the final norm
    assert flops.weight_bytes(D) == 2 * (2 * (layer + 16) + 256 + 8)


def test_train_flops_per_token():
    # forward: 2 per multiply-add over every weight, and per layer
    # 4 * heads * head_dim per attended position, (seq+1)/2 on average
    fwd = 2 * (2 * 576 + 256) + 2 * 4 * 4 * 2 * (9 + 1) / 2
    assert flops.train_flops_per_token(D, 9) == pytest.approx(3 * fwd)


def test_prefill_and_decode():
    prompt = 5
    body = 2 * 2 * 576 * prompt + 2 * 4 * 4 * 2 * prompt * 6 / 2
    assert flops.prefill_flops(D, prompt) == pytest.approx(body + 2 * 256)
    step = flops.decode_step(D, [3, 7])
    assert step["flops"] == 2 * (2 * 1408) + 2 * 4 * 4 * 2 * 10
    kv_per_token = 2 * 2 * 2 * 2 * 2          # k and v, layers, kv, dh, bf16
    assert step["bytes"] == flops.weight_bytes(D) + kv_per_token * 10


def test_least_seconds_takes_the_binding_roof():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_seconds(1000.0, 20.0, peaks) == 10.0
    assert flops.least_seconds(100.0, 50.0, peaks) == 5.0


def test_kernels():
    fwd = flops.xent_call(4, 10, backward=False)
    bwd = flops.xent_call(4, 10, backward=True)
    assert fwd["bytes"] == 4 * 40 and bwd["bytes"] == 8 * 40
    assert flops.adamw_elements(10)["bytes"] == 10 * (2 + 2 + 2 + 16)
