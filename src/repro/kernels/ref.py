"""Pure-jnp oracles for every Pallas kernel. These are the ground truth the
kernel tests assert against, and the CPU fallback paths used by the dry-run."""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------- int8 matmul ----

def quantize_rowwise(x, axis=-1):
    """Symmetric int8 quantization with per-row (last-axis-reduced) scales."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def int8_matmul_ref(x_q, x_scale, w_q, w_scale, out_dtype=jnp.bfloat16):
    """x_q: (M,K) int8, x_scale: (M,1) f32; w_q: (K,N) int8, w_scale: (1,N)."""
    acc = jax.lax.dot_general(
        x_q, w_q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(out_dtype)


def quantized_matmul_ref(x, w, out_dtype=None):
    """End-to-end W8A8 dynamic-quantized matmul (arbitrary leading dims)."""
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    x_q, x_s = quantize_rowwise(x2)
    w_q, w_s = quantize_rowwise(w, axis=0)
    y = int8_matmul_ref(x_q, x_s, w_q, w_s, out_dtype)
    return y.reshape(lead + (w.shape[-1],))


# ------------------------------------------------------- flash attention ----

def mha_ref(q, k, v, *, causal=True, window=0, cap=0.0):
    """Naive masked attention oracle. q: (B,H,Sq,hd), k/v: (B,KVH,Skv,hd).

    GQA: q head h reads kv head h // (H // KVH).
    """
    B, H, Sq, hd = q.shape
    KVH = k.shape[1]
    rep = H // KVH
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    if cap:
        s = cap * jnp.tanh(s / cap)
    qp = jnp.arange(Sq)[:, None] + (k.shape[2] - Sq)   # align ends (decode ok)
    kp = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((Sq, k.shape[2]), bool)
    if causal:
        mask &= kp <= qp
    if window:
        mask &= kp > qp - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


# ---------------------------------------------------------- Mamba2 SSD ----

def ssd_ref(x, dt, a, b, c, *, d_skip=None):
    """Naive per-token SSD recurrence oracle (fp32 state).

    x: (B,S,H,P); dt: (B,S,H) (already softplus'd); a: (H,) negative;
    b, c: (B,S,N) (single group, broadcast over heads). Returns (B,S,H,P).
    """
    Bsz, S, H, P = x.shape
    N = b.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    bf, cf = b.astype(jnp.float32), c.astype(jnp.float32)

    def step(state, inp):
        xt, dtt, bt, ct = inp           # (B,H,P), (B,H), (B,N), (B,N)
        da = jnp.exp(dtt * a)           # (B,H)
        state = (state * da[..., None, None]
                 + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        y = jnp.einsum("bhpn,bn->bhp", state, ct)
        return state, y

    s0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    _, ys = jax.lax.scan(
        step, s0,
        (xf.transpose(1, 0, 2, 3), dtf.transpose(1, 0, 2),
         bf.transpose(1, 0, 2), cf.transpose(1, 0, 2)))
    y = ys.transpose(1, 0, 2, 3)
    if d_skip is not None:
        y = y + d_skip.astype(jnp.float32)[None, None, :, None] * xf
    return y.astype(x.dtype)


def ssd_chunked_ref(x, dt, a, b, c, *, chunk=64, d_skip=None,
                    return_state=False, init_state=None):
    """Chunked (state-space-duality) jnp implementation — the algorithm the
    Pallas kernel implements; also the model's CPU/dry-run path.

    ``return_state=True`` additionally returns the final (B,H,P,N) state —
    used by serving prefill to hand off into incremental decode.
    ``init_state`` seeds the recurrence with an existing (B,H,P,N) state so a
    prompt can be consumed in chunks (serving chunked-prefill admission)."""
    Bsz, S, H, P = x.shape
    N = b.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q
    xf = x.astype(jnp.float32).reshape(Bsz, nc, Q, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bsz, nc, Q, H)
    bf = b.astype(jnp.float32).reshape(Bsz, nc, Q, N)
    cf = c.astype(jnp.float32).reshape(Bsz, nc, Q, N)
    la = dtf * a                                     # (B,nc,Q,H) log-decay
    cum = jnp.cumsum(la, axis=2)                     # inclusive
    total = cum[:, :, -1:, :]                        # (B,nc,1,H)
    # the big rank-5 intra-chunk operands are cast to the INPUT dtype (bf16
    # in production): decay/mask/dt chains fuse into a single low-precision
    # write instead of fp32, halving SSD HBM traffic (EXPERIMENTS.md §Perf
    # zamba2 iteration); fp32 is kept for cumsum, the state scan, and all
    # matmul ACCUMULATORS (preferred_element_type below).
    cdt = x.dtype
    # intra-chunk: y_t += sum_{i<=t} exp(cum_t - cum_i) dt_i (C_t.B_i) x_i
    # NOTE: expressed as two-operand einsums (batched matmuls) — 3-operand
    # forms made XLA materialize a rank-6 (B,nc,Q,K,H,P) intermediate
    # (EXPERIMENTS.md §Perf: 154 GiB peak, 4x FLOP inflation; fixed here).
    g = jnp.einsum("bcqn,bckn->bcqk", cf, bf,
                   preferred_element_type=jnp.float32)   # (B,nc,Q,Q)
    dec = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Q,Q,H) t,i
    mask = (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :])
    m = jnp.where(mask[None, None, :, :, None], jnp.exp(dec), 0.0)
    w = (g[..., None] * m * dtf[:, :, None, :, :]).astype(cdt)
    y_intra = jnp.einsum("bcqkh,bckhp->bcqhp", w, xf.astype(cdt),
                         preferred_element_type=jnp.float32)
    # chunk states: S_c = exp(total) S_{c-1} + sum_i exp(total-cum_i) dt_i x_i B_i
    wi = jnp.exp(total - cum) * dtf                  # (B,nc,Q,H)
    s_in = jnp.einsum("bcqhp,bcqn->bchpn",
                      (xf * wi[..., None]).astype(cdt), bf.astype(cdt),
                      preferred_element_type=jnp.float32)

    # chunk-state recurrence as a STATIC python loop (nc is static, the body
    # is a few elementwise ops): a lax.scan here made the dry-run accounting
    # lie — XLA counts a while body once regardless of trips, and the body is
    # so small that the 2-point unroll probe measured loop-shuttle fusion
    # noise (a NEGATIVE byte marginal) instead of body cost. Fully static,
    # every chunk body is counted exactly in the base compile.
    decay = jnp.exp(total[:, :, 0, :])               # (B,nc,H)
    s = (jnp.zeros((Bsz, H, P, N), jnp.float32) if init_state is None
         else init_state.astype(jnp.float32))
    enters = []
    for ci in range(nc):
        enters.append(s)
        s = s * decay[:, ci, :, None, None] + s_in[:, ci]
    s_final = s
    s_enter = jnp.stack(enters, axis=1)              # (B,nc,H,P,N)
    y_state = jnp.einsum("bcqn,bchpn->bcqhp", cf.astype(cdt),
                         s_enter.astype(cdt),
                         preferred_element_type=jnp.float32) \
        * jnp.exp(cum)[..., None]
    y = (y_intra + y_state).reshape(Bsz, S, H, P)
    if d_skip is not None:
        y = y + d_skip.astype(jnp.float32)[None, None, :, None] * \
            x.astype(jnp.float32)
    if return_state:
        return y.astype(x.dtype), s_final
    return y.astype(x.dtype)


# ----------------------------------------------------- grouped experts ----

def moe_experts_ref(x, wg, wu, wo, tile_e, tile_n, layer, scales=None, *,
                    tm: int):
    """Oracle of ``kernels.moe_experts``: each tile of ``tm`` sorted rows
    through its expert's SwiGLU (weights (L, E, F, D), layer ``layer``);
    empty tiles give 0. ``scales``: the kernel's int8 mode (int8 weights,
    rows quantized per row, the SwiGLU's product per row of each f-block
    of the kernel's width)."""
    from repro.kernels.moe_experts import block_f
    wg, wu, wo = (jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
                  for w in (wg, wu, wo))
    n_t = x.shape[0] // tm
    xt = x.reshape(n_t, tm, -1)
    f32 = jnp.float32
    if scales is None:
        g = jnp.einsum("tmd,tfd->tmf", xt, wg[tile_e],
                       preferred_element_type=f32)
        u = jnp.einsum("tmd,tfd->tmf", xt, wu[tile_e],
                       preferred_element_type=f32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        y = jnp.einsum("tmf,tfd->tmd", h, wo[tile_e],
                       preferred_element_type=f32)
    else:
        gs, us, os_ = (jax.lax.dynamic_index_in_dim(s, layer, keepdims=False)
                       for s in scales)
        i32 = jnp.int32
        xq, xs = quantize_rowwise(xt)
        g = jnp.einsum("tmd,tfd->tmf", xq, wg[tile_e],
                       preferred_element_type=i32).astype(f32) * xs
        u = jnp.einsum("tmd,tfd->tmf", xq, wu[tile_e],
                       preferred_element_type=i32).astype(f32) * xs
        g, u = g * gs[tile_e], u * us[tile_e]
        F = g.shape[-1]
        tf = block_f(F)
        hq, hs = quantize_rowwise(
            (g / (1.0 + jnp.exp(-g)) * u).reshape(n_t, tm, F // tf, tf))
        wob = wo[tile_e].reshape(n_t, F // tf, tf, -1)
        y = jnp.sum(jnp.einsum("tmbf,tbfd->tmbd", hq, wob,
                               preferred_element_type=i32).astype(f32) * hs,
                    axis=2) * os_[tile_e]
    y = jnp.where((tile_n > 0)[:, None, None], y, 0.0)
    return y.reshape(x.shape).astype(x.dtype)
