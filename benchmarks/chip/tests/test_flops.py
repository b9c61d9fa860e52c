"""Model FLOP and byte counts against a hand count for qwen2-1.5b."""
import json
from pathlib import Path

import pytest

from benchmarks.chip import flops
from benchmarks.chip.reference.dense_lm import spec_of

CFG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                  / "qwen2-1.5b.json").read_text())


@pytest.fixture(scope="module")
def spec():
    return spec_of(CFG)


def test_layer_and_head_parameters(spec):
    # q 1536x1536, k and v 1536x256, o 1536x1536, gate/up/down 1536x8960
    per_layer = 1536 * 1536 * 2 + 1536 * 256 * 2 + 3 * 1536 * 8960
    assert per_layer == 46_792_704
    assert flops.layer_matmul_params(spec) == per_layer
    assert flops.head_params(spec) == 151_936 * 1536
    # weights read by one decode step: 28 layers + the tied head, bf16
    assert flops.weight_bytes(spec) == 2 * (28 * per_layer + 151_936 * 1536)
    assert flops.weight_bytes(spec) == 3_087_138_816


def test_kv_bytes_per_token(spec):
    assert flops.kv_bytes_per_token(spec) == 28 * 2 * 2 * 128 * 2 == 28_672


def test_decode_flops_by_hand(spec):
    ctx = 1000
    matmul = 2 * 28 * 46_792_704
    attn = 4 * 28 * 12 * 128 * ctx          # QK^T and PV over 1000 keys
    head = 2 * 151_936 * 1536
    assert flops.decode_flops(spec, ctx) == matmul + attn + head


def test_prefill_flops_by_hand(spec):
    # a 3-token chunk at positions 5, 6, 7 sees 6 + 7 + 8 = 21 keys
    f = flops.prefill_flops(spec, start=5, tokens=3, emits=False)
    assert f == 3 * 2 * 28 * 46_792_704 + 4 * 28 * 12 * 128 * 21
    assert flops.prefill_flops(spec, 5, 3, True) - f == 2 * 151_936 * 1536


def test_iteration_and_decode_bytes(spec):
    it = flops.iteration_flops(spec, [(0, 4, True)], [10, 20])
    assert it == flops.prefill_flops(spec, 0, 4, True) \
        + flops.decode_flops(spec, 10) + flops.decode_flops(spec, 20)
    assert flops.decode_bytes(spec, [10, 20]) == 3_087_138_816 + 30 * 28_672


def test_peaks_are_keyed_by_device_kind():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("cpu")
