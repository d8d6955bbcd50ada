"""The FLOP and byte arithmetic against values worked by hand at Moonlight's
published shapes (benchmark/counts/deepseek_v3.py, through the lookup)."""

import json
import os

import pytest

from benchmark import flops, peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(
        ROOT, "benchmark", "configs", "moonlight_16b_a3b_serve_v5e1.json")) as _f:
    CFG = json.load(_f)
# as a reader finds them: by the configuration's `reference` name
counts = flops.counts_for(
    {"root": ROOT, "paths": ["benchmark", "tests/benchmark"], "config": CFG})


def test_attention_linears_per_token():
    # q 2048x(16x192), kv-down 2048x576, kv-up 512x(16x256), o (16x128)x2048
    want = 2 * (2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048)
    assert want == 27_525_120
    assert counts.attn_linear_flops_per_token(CFG) == want


def test_mlp_per_token():
    assert counts.mlp_flops_per_token(CFG, False) == 2 * 3 * 2048 * 11264 == 138_412_032
    routed = 6 * 2 * 3 * 2048 * 1408
    shared = 2 * 2 * 3 * 2048 * 1408
    router = 2 * 2048 * 64
    assert routed + shared + router == 138_674_176
    assert counts.mlp_flops_per_token(CFG, True) == 138_674_176


def test_nine_layers_per_token():
    want = 9 * 27_525_120 + 138_412_032 + 8 * 138_674_176
    assert want == 1_495_531_520
    assert counts.layers_linear_flops_per_token(CFG) == want


def test_serve_step():
    # 250 rows attending to 50,000 keys in all, 60 of them sampled
    scores = 9 * 2 * 16 * (128 + 64 + 128) * 50_000
    head = 60 * 2 * 2048 * 163840
    assert scores == 4_608_000_000 and head == 40_265_318_400
    assert counts.serve_step_flops(CFG, 250, 50_000, 60) == (
        250 * 1_495_531_520 + scores + head)


def test_paged_mla_call_and_its_roofline():
    # one layer: 250 rows, 50,000 row-keys, 12,000 cached tokens to read
    need = counts.paged_mla_call(CFG, 250, 50_000, 12_000)
    assert need["flops"] == 2 * 16 * (576 + 512) * 50_000 == 1_740_800_000
    assert need["bytes"] == 2 * (12_000 * 576 + 250 * 16 * 576 + 250 * 16 * 512)
    assert need["bytes"] == 22_528_000
    r = flops.roofline(need["flops"], need["bytes"], peaks.peaks_for("TPU v5 lite"))
    assert r["bound"] == "memory"
    assert r["least_s"] == pytest.approx(22_528_000 / 819e9)
    # a long single decode row is bound by memory too, a big chunk by compute
    r = flops.roofline(1e12, 1e6, peaks.peaks_for("TPU v5 lite"))
    assert r["bound"] == "compute" and r["least_s"] == pytest.approx(1e12 / 197e12)


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["ici_bits_per_s"] == 1600e9 and p["source"]
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")
