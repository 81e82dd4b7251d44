"""Operation counts and the peaks table."""
import json

import pytest

from bench import flops
from bench.harness import BENCH
from bench.peaks import peaks

QWEN3_4B = json.loads((BENCH / "configs" / "qwen3-4b.json").read_text())


def test_qwen3_4b_parameters_by_hand():
    d, ff, v, layers = 2560, 9728, 151936, 36
    attn = d * 32 * 128 + 2 * d * 8 * 128 + 32 * 128 * d
    mlp = 3 * d * ff
    norms = 2 * d + 2 * 128                  # two gains, q/k norms
    total = v * d + layers * (attn + mlp + norms) + d
    assert total == 4_022_468_096
    assert flops.param_count(QWEN3_4B) == total


def test_forward_flops_by_hand():
    ctx = 600
    matmuls = 36 * (2560 * 4096 * 2 + 2 * 2560 * 1024 + 3 * 2560 * 9728)
    head = 151936 * 2560
    attention = 36 * 4 * 32 * 128 * ctx
    assert flops.forward_flops(QWEN3_4B, ctx) == 2 * (matmuls + head) \
        + attention
    # the matmuls and tied head are 2 x 4.02 B operations a token
    assert 8.04e9 < 2 * (matmuls + head) < 8.05e9


def test_output_token_flops_counts_each_position():
    got = flops.output_token_flops(QWEN3_4B, 512, 3)
    assert got == sum(flops.forward_flops(QWEN3_4B, a)
                      for a in (512, 513, 514))


def test_peaks_by_device_kind():
    v5e = peaks("TPU v5 lite")
    assert v5e["bf16_flop_s"] == 197e12 and v5e["hbm_bytes_s"] == 819e9
    assert v5e["ici_bits_s"] == 1600e9 and "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        peaks("cpu")
