"""Plain float32 reference of a dense decoder: RMSNorm, grouped-query
attention with rotary positions, SwiGLU MLP, tied embeddings.

It follows the configuration file as run (``phi4-mini-3.8b.json``): rotary
over the whole head (``partial_rotary_factor`` 1.0, the halves of each head
rotated as one pair of blocks), no rope scaling, RMSNorm epsilon
``rms_norm_eps``. It imports nothing of the program and reads only the
configuration file and the weights the benchmark made. Every matrix product
runs at ``highest`` precision; weights are upcast from their served type.
The reference runs one layer at a time, so that a full-width model fits the
chip beside its activations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BUCKET = 512        # sequences pad to a multiple: few compiled shapes


def param_layout(cfg: dict) -> dict:
    """The weight tree the served model takes: (shape, dtype, init) leaves.
    Layer weights are stacked over layers on axis 0."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    ff = cfg["intermediate_size"]
    bf = "bfloat16"
    return {
        "embed": ((cfg["vocab_size"], d), bf, "embed"),
        "final_norm": ((d,), bf, "ones"),
        "groups": {"pos0": {
            "norm_attn": ((L, d), bf, "ones"),
            "attn": {"wq": ((L, d, q), bf, "normal"),
                     "wk": ((L, d, kv), bf, "normal"),
                     "wv": ((L, d, kv), bf, "normal"),
                     "wo": ((L, q, d), bf, "normal")},
            "norm_mlp": ((L, d), bf, "ones"),
            "mlp": {"wi_gate": ((L, d, ff), bf, "normal"),
                    "wi_up": ((L, d, ff), bf, "normal"),
                    "wo": ((L, ff, d), bf, "normal")},
        }},
    }


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: (S, heads, hd); rotate the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    ang = pos[:, None, None].astype(jnp.float32) * jnp.asarray(freqs,
                                                               jnp.float32)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("H", "G", "hd", "eps", "theta"))
def _layer(h, layers, i, *, H, G, hd, eps, theta):
    lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False), layers)
    f = lambda a: a.astype(jnp.float32)
    S = h.shape[0]
    pos = jnp.arange(S)
    x = _rms(h, lp["norm_attn"], eps)
    q = (x @ f(lp["attn"]["wq"])).reshape(S, H, hd)
    k = (x @ f(lp["attn"]["wk"])).reshape(S, G, hd)
    v = (x @ f(lp["attn"]["wv"])).reshape(S, G, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    R = H // G
    k = jnp.repeat(k, R, axis=1)           # query head h reads KV head h // R
    v = jnp.repeat(v, R, axis=1)
    s = jnp.einsum("shd,thd->hst", q, k) * hd ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hst,thd->shd", jax.nn.softmax(s, -1), v)
    h = h + o.reshape(S, H * hd) @ f(lp["attn"]["wo"])
    x = _rms(h, lp["norm_mlp"], eps)
    g = jax.nn.silu(x @ f(lp["mlp"]["wi_gate"])) * (x @ f(lp["mlp"]["wi_up"]))
    return h + g @ f(lp["mlp"]["wo"])


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
    kw = dict(H=cfg["num_attention_heads"], G=cfg["num_key_value_heads"],
              hd=cfg["head_dim"], eps=cfg["rms_norm_eps"],
              theta=cfg["rope_theta"])
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
        layers = params["groups"]["pos0"]
        for i in range(cfg["num_hidden_layers"]):
            h = _layer(h, layers, i, **kw)
        n = S - score_from
        idx = np.minimum(score_from + np.arange(-(-n // 64) * 64), Sp - 1)
        out = _logits(h, jnp.asarray(idx), params["final_norm"],
                      params["embed"], eps=cfg["rms_norm_eps"])
    return np.asarray(out[:n])
