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
    four-chip parity check's."""
    from repro.kernels.paged_attention import paged_attention_impl
    B, G, R, hd, P = (PHI4[k] for k in ("B", "G", "R", "hd", "P"))
    M = PHI4["max_len"] // P
    n_pages = B * M + 1

    def fn(q, kp, vp, ppos, block, pos):
        return paged_attention_impl(q, kp, vp, ppos, block, pos,
                                    kv_scale=kv_scale)

    q_dtype = jnp.float32 if kv_dtype == jnp.float32 else jnp.bfloat16
    hlo = _compile(fn, one_chip,
                   ((B, G, R, hd), q_dtype),
                   ((n_pages, P, G, hd), kv_dtype),
                   ((n_pages, P, G, hd), kv_dtype),
                   ((n_pages, P), jnp.int32),
                   ((B, M), jnp.int32),
                   ((B,), jnp.int32))
    assert "tpu_custom_call" in hlo


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
