"""Config registry: exact assigned dims, analytic param counts, cell grid."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, SHAPES, all_cells, get_config
from repro.models import api

EXPECTED_BILLIONS = {       # within documented substitutions (DESIGN.md)
    "zamba2-2.7b": (1.5, 2.8), "gemma3-12b": (10.5, 13),
    "mistral-large-123b": (115, 130), "phi4-mini-3.8b": (3.4, 4.3),
    "gemma2-27b": (24, 30), "whisper-large-v3": (1.2, 2.4),
    "paligemma-3b": (2.0, 3.2), "mamba2-780m": (0.7, 0.9),
    "olmoe-1b-7b": (6.0, 7.5), "moonlight-16b-a3b": (15.9, 16.0),
}

ASSIGNED = {
    "zamba2-2.7b": dict(n_layers=54, d_model=2560, n_heads=32, n_kv_heads=32,
                        d_ff=10240, vocab_size=32000),
    "gemma3-12b": dict(n_layers=48, d_model=3840, n_heads=16, n_kv_heads=8,
                       d_ff=15360, vocab_size=262144),
    "mistral-large-123b": dict(n_layers=88, d_model=12288, n_heads=96,
                               n_kv_heads=8, d_ff=28672, vocab_size=32768),
    "phi4-mini-3.8b": dict(n_layers=32, d_model=3072, n_heads=24,
                           n_kv_heads=8, d_ff=8192, vocab_size=200064),
    "gemma2-27b": dict(n_layers=46, d_model=4608, n_heads=32, n_kv_heads=16,
                       d_ff=36864, vocab_size=256000),
    "whisper-large-v3": dict(n_layers=32, d_model=1280, n_heads=20,
                             n_kv_heads=20, d_ff=5120, vocab_size=51866),
    "paligemma-3b": dict(n_layers=18, d_model=2048, n_heads=8, n_kv_heads=1,
                         d_ff=16384, vocab_size=257216),
    "mamba2-780m": dict(n_layers=48, d_model=1536, d_ff=0, vocab_size=50280),
    "olmoe-1b-7b": dict(n_layers=16, d_model=2048, n_heads=16,
                        n_kv_heads=16, d_ff=0, vocab_size=50304),
    "moonlight-16b-a3b": dict(n_layers=27, n_dense_layers=1, d_model=2048,
                              n_heads=16, n_kv_heads=16, head_dim=128,
                              kv_lora_rank=512, rope_dim=64, d_ff=11264,
                              vocab_size=163840, rope_theta=50_000.0,
                              norm_eps=1e-5, tie_embeddings=False),
}


@pytest.mark.parametrize("name", list(ARCHS))
def test_assigned_dims(name):
    cfg = ARCHS[name]
    for field, val in ASSIGNED[name].items():
        assert getattr(cfg, field) == val, (name, field)


@pytest.mark.parametrize("name", list(ARCHS))
def test_param_count_in_band(name):
    lo, hi = EXPECTED_BILLIONS[name]
    n = ARCHS[name].param_count() / 1e9
    assert lo <= n <= hi, (name, n)


def test_moe_knobs():
    assert ARCHS["olmoe-1b-7b"].moe.n_experts == 64
    assert ARCHS["olmoe-1b-7b"].moe.top_k == 8
    assert ARCHS["olmoe-1b-7b"].moe.d_expert == 1024
    m = ARCHS["moonlight-16b-a3b"].moe
    assert (m.n_experts, m.top_k, m.d_expert, m.d_shared) == (64, 6, 1408,
                                                              2816)
    assert (m.scoring, m.score_bias, m.scale) == ("sigmoid", True, 2.446)
    assert m.held_range == (0, 64)
    # one chip's share of the four-chip expert-parallel deployment
    ep4 = get_config("moonlight-16b-a3b-ep4")
    assert ep4.moe.held_range == (0, 16) and ep4.moe.n_experts == 64
    assert round(ARCHS["moonlight-16b-a3b"].param_count() / 1e9, 2) == 15.96
    assert round(ep4.param_count() / 1e9, 2) == 5.16


def test_cell_grid():
    cells = list(all_cells())
    assert len(cells) == 40
    runnable = [c for c in cells if c[2]]
    assert len(runnable) == 34
    skipped = {(a.name, s.name) for a, s, ok, _ in cells if not ok}
    assert all(s == "long_500k" for _, s in skipped)
    assert ("mamba2-780m", "long_500k") not in skipped
    assert ("zamba2-2.7b", "long_500k") not in skipped
    assert ("gemma3-12b", "long_500k") not in skipped
    assert ("gemma2-27b", "long_500k") not in skipped


@pytest.mark.parametrize("name", list(ARCHS))
def test_smoke_config_param_count_matches_init(name):
    cfg = get_config(name + "-smoke")
    params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.param_count()


def test_shapes_registry():
    assert SHAPES["train_4k"].seq_len == 4096
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].global_batch == 32
    assert SHAPES["decode_32k"].kind == "decode"
    assert SHAPES["long_500k"].seq_len == 524288
