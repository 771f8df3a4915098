"""Operation and byte counts of the benchmark, against hand counts."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import work  # noqa: E402


def _dims(name):
    return work.dims(json.load(open(ROOT / "bench" / "configs"
                                    / f"{name}.json")))


def test_dims_of_both_configurations():
    q3 = _dims("qwen3-1.7b")
    assert (q3.D, q3.Hq, q3.Hkv, q3.hd, q3.F, q3.V, q3.L) == \
        (2048, 16, 8, 128, 6144, 151936, 28)
    q25 = _dims("qwen2.5-14b-pp4")
    assert (q25.D, q25.Hq, q25.Hkv, q25.hd, q25.F, q25.V, q25.L) == \
        (5120, 40, 8, 128, 13824, 152064, 12)
    assert q3.act_bytes == q25.act_bytes == 2


def test_layer_weights_by_hand():
    # qwen3-1.7b: q 2048x2048, k and v 2048x1024, o 2048x2048, mlp 3x2048x6144
    assert work.layer_matmul_params(_dims("qwen3-1.7b")) == \
        2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048 + 3 * 2048 * 6144
    # qwen2.5-14b: q 5120x5120, k and v 5120x1024, o 5120x5120, mlp 3x5120x13824
    p = work.layer_matmul_params(_dims("qwen2.5-14b-pp4"))
    assert p == 5120 * 5120 * 2 + 2 * 5120 * 1024 + 3 * 5120 * 13824
    assert p == 275_251_200


def test_prefill_flops_by_hand():
    d = _dims("qwen3-1.7b")
    n = 100
    per_layer = 2 * (2048 * 2048 * 2 + 2 * 2048 * 1024 + 3 * 2048 * 6144)
    attn = 4 * 16 * 128 * (n * (n + 1) // 2)
    head = 2 * 2048 * 151936
    assert work.prefill_flops(d, n) == 28 * (per_layer * n + attn) + head


def test_decode_flops_count_only_active_slots_and_valid_lengths():
    d = _dims("qwen2.5-14b-pp4")
    per_tok = 2 * 12 * 275_251_200 + 2 * 5120 * 152064
    lens = [10, 500]
    attn = sum(4 * 12 * 40 * 128 * (n + 1) for n in lens)
    assert work.decode_flops(d, lens) == 2 * per_tok + attn
    assert work.decode_flops(d, []) == 0


def test_decode_attention_call_needs_valid_kv_once():
    d = _dims("qwen3-1.7b")
    flops, nbytes = work.decode_attn_call(d, [0, 99])
    assert flops == 4 * 16 * 128 * (1 + 100)
    kv = (1 + 100) * 2 * 8 * 128 * 2          # K and V rows, bf16
    qo = 2 * (2 * 16 * 128 * 2)               # q in, out, per slot
    assert nbytes == kv + qo


def test_roofline_takes_the_binding_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_s(1000.0, 10.0, peak) == pytest.approx(10.0)
    assert work.roofline_s(10.0, 1000.0, peak) == pytest.approx(100.0)


def test_peaks_table_has_the_v5e():
    peaks = json.load(open(ROOT / "bench" / "peaks.json"))
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
