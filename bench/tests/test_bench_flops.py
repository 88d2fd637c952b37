"""FLOP and byte functions against hand counts at the cells' shapes."""
import json
import pathlib

import pytest

from harness import flops

CFGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def cfg(name):
    return json.loads((CFGS / f"{name}.json").read_text())


def test_phi4_token_flops_by_hand():
    c = cfg("phi4-mini-3.8b")
    d, L, V = 3072, 32, 200064
    # q 3072, k and v 1024 each, o 3072; SwiGLU 3 x 3072 x 8192
    proj = 2 * (d * 3072 + 2 * d * 1024 + 3072 * d + 3 * d * 8192)
    assert proj == 2 * 100_663_296
    ctx = 1000
    attn = 4 * ctx * 24 * 128
    assert flops.token_flops(c, ctx) == L * (proj + attn) + 2 * d * V


def test_phi4_span_is_sum_of_tokens():
    c = cfg("phi4-mini-3.8b")
    want = sum(flops.token_flops(c, p + 1) for p in range(100, 228))
    assert flops.span_flops(c, 100, 228) == pytest.approx(want, rel=1e-12)


def test_mamba2_token_flops_by_hand():
    c = cfg("mamba2-780m")
    d, di, N, nh, W, L, V = 1536, 3072, 128, 48, 4, 48, 50280
    proj = 2 * (d * (2 * di + 2 * N + nh) + di * d)
    conv = 2 * (di + 2 * N) * W
    ssd = 6 * di * N
    assert flops.token_flops(c, 5) == L * (proj + conv + ssd) + 2 * d * V
    assert flops.span_flops(c, 0, 10) == 10 * flops.token_flops(c, 0)


def test_phi4_paged_decode_bytes_by_hand():
    c = cfg("phi4-mini-3.8b")
    # position 40 at page 16 reads pages 0, 1, 2: each K and V of
    # 16 x 8 heads x 128 dims in bf16, plus a 16-entry int32 position row
    page = 2 * 16 * 8 * 128 * 2 + 4 * 16
    assert flops.page_hbm_bytes(16, 8, 128, kv_bytes=2) == page
    q_o = 2 * 24 * 128 * 2
    assert flops.paged_decode_token_bytes(c, 40, 16) == 32 * (3 * page + q_o)
    assert flops.paged_decode_token_flops(c, 40) == 32 * 4 * 41 * 24 * 128


def test_param_bytes_match_the_published_sizes():
    # phi4-mini: 3.84e9 parameters with the tied embedding; mamba2: ~7.8e8
    assert flops.param_bytes(cfg("phi4-mini-3.8b")) / 2 == \
        pytest.approx(3.836e9, rel=0.01)
    assert flops.param_bytes(cfg("mamba2-780m")) / 2 == \
        pytest.approx(7.8e8, rel=0.05)
