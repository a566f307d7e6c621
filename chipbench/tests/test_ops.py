"""The yardstick's arithmetic against numbers worked by hand."""

import json
import os

import pytest

from chipbench.harness import loader
from chipbench.ops import decoder_flops, flash, paged_decode


def _cfg(name):
    with open(os.path.join(loader.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_internlm2_flops_per_trained_token():
    cfg = _cfg("internlm2-1.8b")
    # a layer: q 2048x2048 + k, v 2 x 2048x1024 + o 2048x2048 + 3 x 2048x8192
    layer = 2048 * 2048 * 2 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    assert layer == 62_914_560
    assert decoder_flops.matmul_params(cfg) == 24 * layer + 2048 * 92544
    assert decoder_flops.total_params(cfg) == pytest.approx(1.889e9, rel=1e-3)
    # attention at 4096: fwd 2 products x 2 x 16 x 128 x 4096 / 2 a layer
    attn = 3 * (2 * 2 * 16 * 128 * 4096 / 2) * 24
    assert decoder_flops.attention_flops_per_token(cfg, 4096) == attn
    assert decoder_flops.train_flops_per_token(cfg, 4096) == pytest.approx(
        11.4e9, rel=5e-3)


def test_mistral_cut_flops_per_trained_token():
    cfg = _cfg("mistral-7b-v0.3-cut")
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert cfg["num_hidden_layers"] == 3
    assert decoder_flops.matmul_params(cfg) == 3 * layer + 4096 * 32768
    total = 6 * (3 * layer + 4096 * 32768) + 3 * (2 * 2 * 32 * 128 * 2048) * 3
    assert decoder_flops.train_flops_per_token(cfg, 4096) == total
    assert total == pytest.approx(5.03e9, rel=5e-3)
    # the head's share here against the full 32 layers
    assert 6 * 4096 * 32768 / total == pytest.approx(0.16, abs=0.01)


def test_flash_flops_at_the_training_shape():
    f = flash.flash_flops(2, 4096, 32, 128)
    product = 2 * 2 * 32 * 4096 * 4096 * 128 / 2     # one masked product
    assert product == 137_438_953_472
    assert f["fwd"] == 2 * product and f["dq"] == 3 * product
    assert f["dkv"] == 4 * product and f["total"] == 9 * product
    assert flash.flash_bytes(2, 4096, 32, 8, 128) == 3 * (
        2 * 2 * 4096 * 32 * 128 * 2 + 2 * 2 * 4096 * 8 * 128 * 2)


def test_paged_decode_bytes_for_stated_rows():
    # 48 rows: 24 of 100 tokens (7 pages of 16), 23 of 1000 (63 pages),
    # one parked
    lens = [100] * 24 + [1000] * 23 + [0]
    pages = 24 * 7 + 23 * 63
    kv = 2 * pages * 16 * 8 * 128 * 2
    qo = 2 * 47 * 16 * 128 * 2
    assert paged_decode.paged_decode_bytes(lens, 8, 16, 128, 16) == kv + qo
    assert kv == 105_971_712
    assert paged_decode.paged_decode_flops(lens, 16, 128) == \
        4 * 16 * 128 * (24 * 100 + 23 * 1000)
