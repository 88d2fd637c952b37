"""Compile-only rehearsal of every Pallas kernel for a described TPU v5e.

Interpret mode runs a kernel's body on the CPU but never asks Mosaic (the
TPU kernel compiler) whether it accepts the block shapes, memory spaces and
primitives. These tests lower and compile each kernel at the published
widths of the configuration that uses it, for a ``v5e:2x2`` topology that
is described, not attached: a tiling or lowering refusal fails here
instead of on the chip. Nothing runs, so values are checked elsewhere
(the interpret-mode parity tests and ``chip_smoke.py``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    """Lower ``fn`` for the described chip and compile it; returns the HLO
    text of the compiled executable."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# phi4-mini-3.8b decode on one chip: 8 slots x 2048 tokens, 16-token pages
PHI4 = dict(B=8, G=8, R=3, hd=128, P=16, max_len=2048)


@pytest.mark.parametrize("kv_dtype,kv_scale", [(jnp.bfloat16, 0.0),
                                               (jnp.int8, 0.05),
                                               (jnp.float32, 0.0)])
def test_paged_attention_compiles(one_chip, kv_dtype, kv_scale):
    """bf16 and int8 pages serve phi4 on one chip; float32 pages are the
    four-chip parity check's. The pools are stacked over layers, as the
    layer scan carries them, and the kernel reads one by index."""
    from repro.kernels.paged_attention import paged_attention_impl
    B, G, R, hd, P = (PHI4[k] for k in ("B", "G", "R", "hd", "P"))
    M = PHI4["max_len"] // P
    n_pages, L = B * M + 1, 4

    def fn(q, kp, vp, ppos, block, pos, layer):
        return paged_attention_impl(q, kp, vp, ppos, block, pos, layer,
                                    kv_scale=kv_scale)

    q_dtype = jnp.float32 if kv_dtype == jnp.float32 else jnp.bfloat16
    hlo = _compile(fn, one_chip,
                   ((B, G, R, hd), q_dtype),
                   ((L, n_pages, P, G, hd), kv_dtype),
                   ((L, n_pages, P, G, hd), kv_dtype),
                   ((L, n_pages, P), jnp.int32),
                   ((B, M), jnp.int32),
                   ((B,), jnp.int32), ((), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_phi4_megastep_carries_the_paged_pool(one_chip):
    """The megastep at phi4's published widths and serving sizes (16 slots,
    1,792 pages of 16, K=8, caches donated), cut to 2 layers: the layer
    scan carries the stacked K/V pool and the kernel reads its layer by
    index, so no layer's pool is sliced out, written back into the stack,
    or copied whole, and no second pool's worth of temporaries exists."""
    import dataclasses
    import re
    from types import SimpleNamespace
    from repro.configs import get_config
    from repro.models import api
    from repro.train.step import make_paged_megastep
    L, B, P, n_pages, max_len = 2, 16, 16, 1792, 4096
    cfg = dataclasses.replace(get_config("phi4-mini-3.8b"), n_layers=L)
    spec = SimpleNamespace(n_pages=n_pages, page_size=P,
                           max_pages=max_len // P)
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = place(api.abstract(cfg))
    caches = place(api.abstract_caches(cfg, B, max_len, paged=spec))
    row = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    alive = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)
    step = make_paged_megastep(cfg, k=8, use_kernel=True,
                               dynamic_scatter=True)
    compiled = jax.jit(step, donate_argnums=(7,)).lower(
        params, row, row, alive, row, row, row, caches).compile()

    page = f"{n_pages},{P},{cfg.n_kv_heads},{cfg.resolved_head_dim}]"
    pools = (f"bf16[{L},{page}", f"bf16[1,{page}", f"bf16[{page}")
    moved = []
    for line in compiled.as_text().splitlines():
        m = re.search(r"= (.*?) ([\w-]+)\(", line)
        if (m and m.group(2) in ("dynamic-slice", "dynamic-update-slice",
                                 "copy", "copy-start")
                and any(p in m.group(1) for p in pools)):
            moved.append(line.strip()[:160])
    assert not moved, moved
    kv_bytes = sum(c.kp.size * c.kp.dtype.itemsize * 2 for c in caches)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < kv_bytes, (temp, kv_bytes)


@pytest.mark.parametrize("rows", [8, 200])
def test_int8_matmul_compiles(one_chip, rows):
    """8 rows is a phi4 decode batch; 200 is a ragged prefill chunk, which
    the kernel pads to its row block."""
    from repro.kernels.int8_matmul import int8_matmul
    K, N = 3072, 8192
    hlo = _compile(int8_matmul, one_chip,
                   ((rows, K), jnp.int8), ((rows, 1), jnp.float32),
                   ((K, N), jnp.int8), ((1, N), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_ssd_scan_compiles(one_chip):
    """mamba2-780m widths: 48 heads of 64, state 128, chunk 128."""
    from repro.kernels.ssd_scan import ssd_scan
    B, L, H, P, N = 1, 2048, 48, 64, 128
    hlo = _compile(ssd_scan, one_chip,
                   ((B, L, H, P), jnp.bfloat16), ((B, L, H), jnp.float32),
                   ((H,), jnp.float32), ((B, L, N), jnp.bfloat16),
                   ((B, L, N), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("batch,dtype", [(1, jnp.bfloat16), (2, jnp.bfloat16),
                                         (1, jnp.float32)])
def test_ring_hop_compiles(one_chip, batch, dtype):
    """One ring-prefill hop at phi4 heads (24 query / 8 KV): a 512-token
    chunk over 2048 tokens of context split across four shards. Admission
    runs it at batch 1."""
    from repro.kernels.ring_attention import _hop
    H, KVH, hd, Cl, Ll = 24, 8, 128, 128, 512

    def fn(qf, kf, vf, qp, kvp, m, l, acc):
        return _hop(qf, kf, vf, qp, kvp, m, l, acc, window=0, cap=0.0,
                    kv_scale=0.0, interpret=False)

    hlo = _compile(fn, one_chip,
                   ((batch, H, Cl, hd), dtype),
                   ((batch, KVH, Ll, hd), dtype),
                   ((batch, KVH, Ll, hd), dtype),
                   ((batch, Cl), jnp.int32), ((batch, Ll), jnp.int32),
                   ((batch, H, Cl, 1), jnp.float32),
                   ((batch, H, Cl, 1), jnp.float32),
                   ((batch, H, Cl, hd), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention
    B, H, KVH, S, hd = 1, 24, 8, 2048, 128
    hlo = _compile(flash_attention, one_chip,
                   ((B, H, S, hd), jnp.bfloat16),
                   ((B, KVH, S, hd), jnp.bfloat16),
                   ((B, KVH, S, hd), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


# moonlight-16b-a3b (the four-chip deployment's one-chip share) decode: 48
# slots x 2688 tokens, 16-token pages, 26 expert layers of 16 held experts
MOON = dict(B=48, H=16, W=640, dv=512, P=16, max_len=2688, L=26)


@pytest.mark.parametrize("kv_dtype,kv_scale", [(jnp.bfloat16, 0.0),
                                               (jnp.int8, 0.05)])
def test_paged_latent_attention_compiles(one_chip, kv_dtype, kv_scale):
    """The latent pool at bf16, and int8 for the int8 rung."""
    from repro.kernels.paged_attention import paged_latent_attention_impl
    B, H, W, dv, P, L = (MOON[k] for k in ("B", "H", "W", "dv", "P", "L"))
    M = MOON["max_len"] // P
    n_pages = B * M + 1

    def fn(q, cp, ppos, block, pos, layer):
        return paged_latent_attention_impl(q, cp, ppos, block, pos, layer,
                                           dv=dv, scale=192 ** -0.5,
                                           kv_scale=kv_scale)

    hlo = _compile(fn, one_chip,
                   ((B, H, W), jnp.bfloat16),
                   ((L, n_pages, P, W), kv_dtype),
                   ((L, n_pages, P), jnp.int32),
                   ((B, M), jnp.int32), ((B,), jnp.int32), ((), jnp.int32))
    assert "paged_latent_decode" in hlo


@pytest.mark.parametrize("tokens,quant", [
    pytest.param(48, False, id="48"), pytest.param(128, False, id="128"),
    pytest.param(48, True, id="48-int8"),
    pytest.param(128, True, id="128-int8")])
def test_moe_experts_compiles(one_chip, tokens, quant):
    """A decode batch of 48 rows and a 128-token admission chunk, top-6 of
    64 experts, through 16 held experts of width 1408; bf16, and the int8
    rung's mode (one layer's int8 weights and channel scales)."""
    from repro.kernels.moe_experts import moe_experts
    from repro.models.moe import row_tile
    L, E, F, D, k = MOON["L"], 16, 1408, 2048, 6
    tm = row_tile(tokens, k, 64)
    n_tiles = min(tokens * k, (tokens * k + E * (tm - 1)) // tm)
    if quant:
        L, wdt = 1, jnp.int8
        scales = (((L, E, 1, F), jnp.float32), ((L, E, 1, F), jnp.float32),
                  ((L, E, 1, D), jnp.float32))
    else:
        wdt, scales = jnp.bfloat16, ()

    def fn(x, wg, wu, wo, tile_e, tile_n, layer, *s):
        return moe_experts(x, wg, wu, wo, tile_e, tile_n, layer, s or None,
                           tm=tm)

    hlo = _compile(fn, one_chip,
                   ((n_tiles * tm, D), jnp.bfloat16),
                   ((L, E, F, D), wdt), ((L, E, F, D), wdt),
                   ((L, E, F, D), wdt),
                   ((n_tiles,), jnp.int32), ((n_tiles,), jnp.int32),
                   ((), jnp.int32), *scales)
    assert "moe_experts" in hlo
