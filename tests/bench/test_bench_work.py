"""``bench/work.py`` against hand arithmetic at Granite and Mixtral
shapes."""
import json

import pytest

import tiny_cell
import work

GRANITE = json.loads((tiny_cell.BENCH / "configs"
                      / "granite-moe-1b-a400m.json").read_text())
MIXTRAL = json.loads((tiny_cell.BENCH / "configs"
                      / "mixtral-offload.l2.json").read_text())


def test_granite_flops_per_token():
    # per layer: q,o 1024x1024 and k,v 1024x512 -> 2*1024*64*(32+16)
    proj = 2 * 1024 * 64 * (2 * 16 + 2 * 8)
    attn = 4 * 16 * 64 * 101            # position 100 sees 101 keys
    moe = 2 * 1024 * 32 + 8 * 3 * 2 * 1024 * 512
    head = 2 * 1024 * 49155
    assert work.flops_token(GRANITE, 100, True) == 24 * (
        proj + attn + moe) + head
    assert work.flops_token(GRANITE, 100, False) == 24 * (proj + attn + moe)


def test_granite_weight_bytes():
    qkv = 1024 * 64 * (16 + 2 * 8) * 2       # 16 bits
    wo = 16 * 64 * 1024 * 4 / 8               # 4 bits
    router = 1024 * 32 * 4                    # float32
    experts = 8 * 3 * 1024 * 512 * 3 / 8      # top-8 at 3 bits
    norms = 2 * 1024 * 2
    head = 49155 * 1024 * 2                   # tied table read once
    want = 24 * (qkv + wo + router + experts + norms) + head + 1024 * 2
    assert work.weight_bytes(GRANITE) == pytest.approx(want)
    assert 320e6 < want < 340e6


def test_mixtral_decode_step_bytes():
    kv = 2 * 2 * 8 * 128 * 2                  # layers * (k, v) * heads * hd
    weights = work.weight_bytes(MIXTRAL)
    experts = 2 * 3 * 4096 * 14336 * 2 / 8    # top-2 at 2 bits, per layer
    assert weights > 2 * experts
    flops, byts = work.decode_step(MIXTRAL, [9, 99])
    assert byts == pytest.approx(weights + (11 + 101) * kv + 2 * 4096 * 2)
    assert flops == work.flops_token(MIXTRAL, 9, True) + work.flops_token(
        MIXTRAL, 99, True)


def test_prefill_chunk_counts_one_head_row():
    f, b = work.prefill_chunk(GRANITE, 64, 128, last=True)
    f0, _ = work.prefill_chunk(GRANITE, 64, 128, last=False)
    assert f - f0 == 2 * 1024 * 49155
    assert f0 == sum(work.flops_token(GRANITE, p, False)
                     for p in range(64, 128))
    kv = 24 * 2 * 8 * 64 * 2
    assert b == pytest.approx(work.weight_bytes(GRANITE) + 128 * kv
                              + 64 * (kv + 1024 * 2))


def test_sliding_window_caps_attention():
    m = dict(MIXTRAL, model=dict(MIXTRAL["model"], sliding_window=16))
    assert work.visible(m["model"], 100) == 16
    assert work.visible(MIXTRAL["model"], 100) == 101
