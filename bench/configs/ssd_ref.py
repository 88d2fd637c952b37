"""Plain float32 reference of a Mamba2 (SSD) language model: RMSNorm,
projections to z, x, B, C and dt, a causal depthwise convolution with SiLU,
the state-space recurrence run one token at a time, the gated RMSNorm and
the output projection, with tied embeddings.

It follows the configuration file as run (``mamba2-780m.json``): one group
for B and C, no conv bias, RMSNorm epsilon ``norm_epsilon``, the residual in
float32 here. The recurrence is the sequential form

    state_t = exp(dt_t * A) * state_{t-1} + dt_t * x_t (outer) B_t
    y_t     = state_t . C_t + D * x_t

with dt_t = softplus(x W_dt + dt_bias) and A = -exp(A_log), per head. It
imports nothing of the program and reads only the configuration file and
the weights the benchmark made. Matrix products run at ``highest``
precision, one layer at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BUCKET = 256


def _dims(cfg: dict):
    s = cfg["ssm_cfg"]
    d = cfg["d_model"]
    di = s["expand"] * d
    return d, di, di // s["headdim"], s["headdim"], s["d_state"], s["d_conv"]


def param_layout(cfg: dict) -> dict:
    """The weight tree the served model takes: (shape, dtype, init) leaves.
    Layer weights are stacked over layers on axis 0."""
    d, di, nh, _, N, W = _dims(cfg)
    L = cfg["n_layer"]
    bf, f32 = "bfloat16", "float32"
    return {
        "embed": ((cfg["vocab_size"], d), bf, "embed"),
        "final_norm": ((d,), bf, "ones"),
        "groups": {"pos0": {
            "norm": ((L, d), bf, "ones"),
            "mixer": {
                "in_z": ((L, d, di), bf, "normal"),
                "in_x": ((L, d, di), bf, "normal"),
                "in_bc": ((L, d, 2 * N), bf, "normal"),
                "in_dt": ((L, d, nh), bf, "normal"),
                "conv_x": ((L, di, W), bf, "conv"),
                "conv_bc": ((L, 2 * N, W), bf, "conv"),
                "a_log": ((L, nh), f32, "ssm_a"),
                "d_skip": ((L, nh), bf, "ones"),
                "dt_bias": ((L, nh), f32, "ssm_dt"),
                "gate_norm": ((L, di), bf, "ones"),
                "out_proj": ((L, di, d), bf, "normal"),
            }}},
    }


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _conv(u, w):
    """Causal depthwise conv: out[t] = sum_j u[t - (W-1) + j] w[:, j]."""
    W = w.shape[-1]
    S = u.shape[0]
    pad = jnp.concatenate([jnp.zeros((W - 1, u.shape[1]), u.dtype), u])
    return jax.nn.silu(sum(pad[j:j + S] * w[:, j] for j in range(W)))


@functools.partial(jax.jit, static_argnames=("nh", "hd", "N", "eps"))
def _layer(h, layers, i, *, nh, hd, N, eps):
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False).astype(jnp.float32), layers)
    m = p["mixer"]
    S = h.shape[0]
    x = _rms(h, p["norm"], eps)
    z = x @ m["in_z"]
    xs = _conv(x @ m["in_x"], m["conv_x"])
    bc = _conv(x @ m["in_bc"], m["conv_bc"])
    b, c = bc[:, :N], bc[:, N:]
    dt = jax.nn.softplus(x @ m["in_dt"] + m["dt_bias"])       # (S, nh)
    a = -jnp.exp(m["a_log"])                                   # (nh,)
    xh = xs.reshape(S, nh, hd)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (state * jnp.exp(dt_t * a)[:, None, None]
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        y = state @ c_t + m["d_skip"][:, None] * x_t
        return state, y

    _, y = jax.lax.scan(step, jnp.zeros((nh, hd, N), jnp.float32),
                        (xh, dt, b, c))
    y = y.reshape(S, nh * hd)
    y = _rms(y * jax.nn.silu(z), m["gate_norm"], eps)
    return h + y @ m["out_proj"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(h, idx, final_norm, embed, *, eps):
    return _rms(h[idx], final_norm, eps) @ embed.astype(jnp.float32).T


def logits_at(params, tokens, score_from: int, cfg: dict) -> np.ndarray:
    """Logits (n, V) at positions ``score_from .. len(tokens) - 1`` of the
    causal forward pass over ``tokens``."""
    S = len(tokens)
    Sp = -(-S // BUCKET) * BUCKET
    toks = np.zeros(Sp, np.int32)
    toks[:S] = tokens
    _, _, nh, hd, N, _ = _dims(cfg)
    eps = cfg["norm_epsilon"]
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
        layers = params["groups"]["pos0"]
        for i in range(cfg["n_layer"]):
            h = _layer(h, layers, i, nh=nh, hd=hd, N=N, eps=eps)
        n = S - score_from
        idx = np.minimum(score_from + np.arange(-(-n // 64) * 64), Sp - 1)
        out = _logits(h, jnp.asarray(idx), params["final_norm"],
                      params["embed"], eps=eps)
    return np.asarray(out[:n])
