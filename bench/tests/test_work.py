"""Work counts and peaks against figures worked out by hand at the
published widths of qwen3-0.6b and OLMo-1B; the counts' parts are the
dense family's (``families/dense.py``)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import peaks, plugins, work  # noqa: E402


def conf(name):
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        return json.load(f)


QWEN, OLMO = conf("qwen3-0.6b"), conf("olmo-1b")
dense = plugins.family(QWEN)


def test_parameter_counts():
    # qwen3: 1024*(16+2*8)*128 + 16*128*1024 + 3*1024*3072 per layer
    assert dense.layer_matmul_params(QWEN) == 15_728_640
    assert dense.matmul_params(QWEN) == 28 * 15_728_640 + 151_936 * 1024
    # + 57 RMSNorm scales of 1024, 56 qk-norm scales of 128, tied table
    assert dense.weight_bytes(QWEN) == 2 * (440_401_920 + 58_368 + 7_168
                                           + 155_582_464)
    # olmo: 2048*48*128 + 16*128*2048 + 3*2048*8192, no norm weights
    assert dense.layer_matmul_params(OLMO) == 67_108_864
    assert dense.weight_bytes(OLMO) == 2 * (16 * 67_108_864 + 50_304 * 2048)


def test_kv_bytes_per_token():
    assert dense.kv_bytes_per_token(QWEN) == 114_688
    assert dense.kv_bytes_per_token(OLMO) == 131_072


def test_decode_step():
    f, b = work.decode_step(QWEN, 32, 32 * 300)
    assert f == pytest.approx(2 * 595_984_384 * 32 + 229_376 * 9_632)
    assert b == 1_192_099_840 + 114_688 * 9_632
    # memory-bound: 2.297 GB at 819 GB/s
    assert peaks.least_seconds(f, b, "TPU v5 lite") == pytest.approx(
        b / 819e9)


def test_prefill_counts_the_prompt_once():
    f, b = work.prefill(OLMO, 256)
    layers = 16 * 67_108_864
    attn = 4 * 16 * 16 * 128 * 256 * 257 / 2
    assert f == pytest.approx(2 * layers * 256 + attn + 2 * 50_304 * 2048)
    assert b == dense.weight_bytes(OLMO) + 131_072 * 256


def test_train_step_qwen_4x4096():
    f, b = work.train_step(QWEN, 4, 4096)
    dense = 6 * 595_984_384 * 16_384
    attn = 3 * 229_376 * 4 * 4096 * 4097 / 2
    assert f == pytest.approx(dense + attn)
    assert f == pytest.approx(81.68e12, rel=1e-3)
    # compute-bound on a v5e: 0.415 s
    assert peaks.least_seconds(f, b, "TPU v5 lite") == pytest.approx(
        f / 197e12)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
