"""FLOP and byte functions against hand counts, and the peak table."""

import json
import pathlib

import pytest

from bench import flops as FL
from bench.peaks import PEAKS, UnknownDevice, peak_for

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_olmo_train_flops_per_token():
    # per layer 4 d^2 + 3 d f = 16.8M + 50.3M; 8 layers; unembed d V;
    # attention 2 S d per layer at S = 2048; forward x3
    d, f, V, L, S = 2048, 8192, 50304, 8, 2048
    fwd = 2 * (L * (4 * d * d + 3 * d * f) + d * V) + L * 2 * d * (S + 1)
    assert FL.train_flops_per_token(_cfg("olmo-1b-8l"), S) == pytest.approx(3 * fwd)
    assert FL.train_flops_per_token(_cfg("olmo-1b-8l"), S) == pytest.approx(4.04e9, rel=0.01)


def test_flash_call_flops_and_bytes():
    # 4 x 16 heads, 2048 positions, head 128, causal: 2 * BH * S^2 * D
    assert FL.flash_fwd_flops(64, 2048, 128) == pytest.approx(6.87e10, rel=0.01)
    assert FL.flash_fwd_flops(64, 2048, 128, causal=False) == 4 * 64 * 2048 ** 2 * 128
    assert FL.flash_fwd_bytes(64, 2048, 128) == 4 * 64 * 2048 * 128 * 2


def test_mixtral_decode_token_flops_count_top2_only():
    cfg = _cfg("mixtral-8x7b-2l")
    attn = 4096 * 4096 * 2 + 2 * 4096 * 1024
    ffn = 4096 * 8 + 2 * 3 * 4096 * 14336
    assert FL.decoder_token_flops(cfg) == 2 * (2 * (attn + ffn) + 4096 * 32000)


def test_mixtral_decode_step_bytes():
    cfg = _cfg("mixtral-8x7b-2l")
    # 32 tokens reach all 8 experts (to 1e-3); K/V at bf16, 2 layers x 8
    # heads x 128 x (k, v) per position
    w = 2 * (4096 * 6144 + 4096 * 4096 + 4096 * 8 + 8 * 3 * 4096 * 14336) + 4096 * 32000
    got = FL.decode_step_bytes(cfg, tokens=32, context=1000)
    assert got == pytest.approx(2 * (w + 1000 * 2 * 2 * 8 * 128), rel=1e-3)
    assert 5.9e9 < got < 6.1e9


def test_peaks_are_keyed_by_device_kind():
    assert peak_for("TPU v5 lite").flops_bf16 == 197e12
    assert PEAKS["TPU v5 lite"].hbm_bytes_s == 819e9
    with pytest.raises(UnknownDevice):
        peak_for("TPU v9 imaginary")
