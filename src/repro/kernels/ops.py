"""Public wrappers for the Pallas kernels with platform dispatch.

On TPU the Pallas kernels run natively; on CPU (the tests, and the dry-run's
512-way host platform) the pure-jnp references lower instead, so
``lower().compile()`` works everywhere and kernels are validated via
``interpret=True`` in tests.

``DISPATCH_COUNTS`` records, while tracing, which side each call took
(``int8_matmul`` / ``int8_matmul_ref``, ``ssd_scan`` / ``ssd_ref``,
``moe_experts`` / ``moe_experts_ref``), so a
chip run can prove it never landed on a reference.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.moe_experts import moe_experts
from repro.kernels.ssd_scan import ssd_scan

DISPATCH_COUNTS: "collections.Counter[str]" = collections.Counter()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def quantized_matmul(x, w):
    """W8A8 dynamic-quantized matmul (the Pliant lower-precision knob)."""
    if _on_tpu():
        DISPATCH_COUNTS["int8_matmul"] += 1
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        x_q, x_s = ref.quantize_rowwise(x2)
        w_q, w_s = ref.quantize_rowwise(w, axis=0)
        y = int8_matmul(x_q, x_s, w_q, w_s, out_dtype=x.dtype)
        return y.reshape(lead + (w.shape[-1],))
    DISPATCH_COUNTS["int8_matmul_ref"] += 1
    return ref.quantized_matmul_ref(x, w)


def bf16_matmul(x, w):
    return jnp.einsum("...k,kn->...n", x, w)


def matmul(precision: str):
    """Matmul dispatch by approximation precision: 'bf16' | 'int8'."""
    if precision == "int8":
        return quantized_matmul
    return bf16_matmul


def grouped_experts(x, wg, wu, wo, tile_e, tile_n, layer, scales=None, *,
                    tm: int, use_kernel=None, interpret: bool = False):
    """The held experts' SwiGLU over expert-sorted row tiles, weights
    (L, E, F, D) at ``layer``: the Pallas ``moe_experts`` kernel on TPU (or
    in interpret mode), jnp elsewhere. ``scales``: int8 weights and their
    channel scales (the kernel's int8 mode)."""
    if use_kernel is None:
        use_kernel = _on_tpu()
    if use_kernel or interpret:
        DISPATCH_COUNTS["moe_experts"] += 1
        return moe_experts(x, wg, wu, wo, tile_e, tile_n, layer, scales,
                           tm=tm, interpret=interpret)
    DISPATCH_COUNTS["moe_experts_ref"] += 1
    return ref.moe_experts_ref(x, wg, wu, wo, tile_e, tile_n, layer, scales,
                               tm=tm)


def ssd(x, dt, a, b, c, *, chunk=128, d_skip=None):
    """Mamba2 SSD scan: Pallas on TPU, chunked jnp elsewhere."""
    if _on_tpu():
        DISPATCH_COUNTS["ssd_scan"] += 1
        y = ssd_scan(x, dt, a, b, c, chunk=chunk)
        if d_skip is not None:
            y = (y.astype(jnp.float32)
                 + d_skip.astype(jnp.float32)[None, None, :, None]
                 * x.astype(jnp.float32)).astype(x.dtype)
        return y
    DISPATCH_COUNTS["ssd_ref"] += 1
    return ref.ssd_chunked_ref(x, dt, a, b, c, chunk=chunk, d_skip=d_skip)
