"""FLOP counts against hand counts, and the peak table."""

from __future__ import annotations

import json
import pathlib

import pytest

import flops

BENCH = pathlib.Path(__file__).resolve().parents[1]


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_active_params_match_hand_counts():
    # mixtral-d2, 3 layers: per layer attention 1024*1024*2 + 2*1024*256,
    # router 1024*18, two experts 2*3*1024*3584; plus the 32000 x 1024 head.
    per_layer = 2_621_440 + 18_432 + 22_020_096
    assert flops.layer_active_params(config("mixtral-d2-3l")) == per_layer
    assert flops.active_params(config("mixtral-d2-3l")) == \
        3 * per_layer + 32_768_000
    assert round(flops.active_params(config("mixtral-d2-3l")) / 1e6, 1) \
        == 106.7
    # mixtral-d1 (HeterMoE Table 2): mixtral-d2's widths at 8 layers and
    # 24 experts.
    d1 = dict(config("mixtral-d2"), n_layers=8, n_experts=24)
    assert round(flops.active_params(d1) / 1e6, 1) == 230.1


def test_train_step_flops():
    m = config("mixtral-d2-3l")
    attn = 3 * 4.0 * 1024 * 4096 * 4097 / 2  # causal forward, all layers
    want = 6.0 * flops.active_params(m) * 8192 + 3.0 * 2 * attn
    assert flops.train_step_flops(m, 2, 4096) == pytest.approx(want)


def test_prefill_chunk_is_its_tokens():
    m = config("mixtral-d2")
    chunk = flops.prefill_chunk_flops(m, 128, 16)
    per_token = sum(flops.serve_token_flops(m, 128 + i + 1, False)
                    for i in range(16))
    head = 2.0 * flops.head_params(m)
    assert chunk == pytest.approx(per_token + head)


def test_peaks_name_their_source():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
