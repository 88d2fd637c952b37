"""Plain float32 reference of a DeepSeek-V3-style decoder (Moonlight):
RMSNorm; latent attention (MLA) in the expanded form; leading dense SwiGLU
layers; then layers of sigmoid-routed experts plus shared experts; an
untied output head.

It follows the configuration file as run (``moonlight-16b-a3b.json``):
- attention: q straight from x (``q_lora_rank`` null), H heads of
  ``qk_nope_head_dim`` + ``qk_rope_head_dim``; ``kv_lora_rank`` latent,
  RMS-normed, expanded per head into the nope key and the
  ``v_head_dim`` value; one shared rotary key joins every head's key;
  softmax scale (nope + rope)^-0.5; causal;
- experts: sigmoid scores over ``deployment.published_n_routed_experts``,
  the top ``num_experts_per_tok`` picked by score plus
  ``e_score_correction_bias``, weighted by score renormalised over the
  picks (``norm_topk_prob``) times ``routed_scaling_factor``; only picks on
  the ``n_routed_experts`` held here (from ``deployment.held_experts``)
  contribute, each through its own SwiGLU of ``moe_intermediate_size``;
  the ``n_shared_experts`` act as one SwiGLU of their summed width, added
  to every token;
- the first ``first_k_dense_replace`` layers use a SwiGLU of
  ``intermediate_size`` instead of experts.

Departures from the published description, as in the configuration file:
rotary pairs are the two halves of the rope dims (the published code
de-interleaves its weight columns first, a fixed relabelling of random
weights); the experts held elsewhere add nothing (one chip's share of an
expert-parallel deployment). It imports nothing of the program, reads the
configuration file and the weights the benchmark made, runs every matrix
product at ``highest`` precision on weights upcast from their served type,
and runs one layer at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BUCKET = 512        # sequences pad to a multiple: few compiled shapes


def _sizes(cfg: dict) -> dict:
    dep = cfg["deployment"]
    return dict(
        d=cfg["hidden_size"], H=cfg["num_attention_heads"],
        hd=cfg["qk_nope_head_dim"], hv=cfg["v_head_dim"],
        hr=cfg["qk_rope_head_dim"], r=cfg["kv_lora_rank"],
        E=dep["published_n_routed_experts"], lo=dep["held_experts"][0],
        n=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
        fe=cfg["moe_intermediate_size"],
        fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        ff=cfg["intermediate_size"], L0=cfg["first_k_dense_replace"],
        L=cfg["num_hidden_layers"], V=cfg["vocab_size"])


def param_layout(cfg: dict) -> dict:
    """The weight tree the served model takes: (shape, dtype, init) leaves.
    Layer weights are stacked over layers on axis 0; expert matrices are
    (experts, width, hidden)."""
    z = _sizes(cfg)
    d, H, hd, hv, hr, r = z["d"], z["H"], z["hd"], z["hv"], z["hr"], z["r"]
    bf = "bfloat16"

    def attn(n):
        return {"wq": ((n, d, H * (hd + hr)), bf, "normal"),
                "wkv_a": ((n, d, r + hr), bf, "normal"),
                "kv_norm": ((n, r), bf, "ones"),
                "wk_b": ((n, r, H * hd), bf, "normal"),
                "wv_b": ((n, r, H * hv), bf, "normal"),
                "wo": ((n, H * hv, d), bf, "normal")}

    def swiglu(n, f):
        return {"wi_gate": ((n, d, f), bf, "normal"),
                "wi_up": ((n, d, f), bf, "normal"),
                "wo": ((n, f, d), bf, "normal")}

    L0, L1, n, fe = z["L0"], z["L"] - z["L0"], z["n"], z["fe"]
    return {
        "embed": ((z["V"], d), bf, "embed"),
        "final_norm": ((d,), bf, "ones"),
        "unembed": ((d, z["V"]), bf, "normal"),
        "lead": {"norm_attn": ((L0, d), bf, "ones"), "attn": attn(L0),
                 "norm_mlp": ((L0, d), bf, "ones"),
                 "mlp": swiglu(L0, z["ff"])},
        "groups": {"pos0": {
            "norm_attn": ((L1, d), bf, "ones"), "attn": attn(L1),
            "norm_mlp": ((L1, d), bf, "ones"),
            "moe": {"wg": ((L1, d, z["E"]), bf, "normal"),
                    "wi_gate": ((L1, n, fe, d), bf, "normal"),
                    "wi_up": ((L1, n, fe, d), bf, "normal"),
                    "wo": ((L1, n, fe, d), bf, "normal"),
                    # e_score_correction_bias: a constant picks as zero does
                    "score_bias": ((L1, z["E"]), bf, "ones"),
                    "shared": swiglu(L1, z["fs"])},
        }},
    }


def _f(a):
    return a.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f(scale)


def _rope(x, pos, theta):
    """x: (S, heads, hr); rotate the two halves of the rope dims."""
    hr = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hr, 2) / hr))
    ang = pos[:, None, None].astype(jnp.float32) * jnp.asarray(freqs,
                                                               jnp.float32)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., : hr // 2], x[..., hr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, p):
    return (jax.nn.silu(x @ _f(p["wi_gate"])) * (x @ _f(p["wi_up"]))) \
        @ _f(p["wo"])


def _attention(h, lp, z, eps, theta):
    """Expanded MLA over the whole (causal) sequence; returns h + attn."""
    S = h.shape[0]
    H, hd, hv, hr, r = z["H"], z["hd"], z["hv"], z["hr"], z["r"]
    pos = jnp.arange(S)
    a = lp["attn"]
    x = _rms(h, lp["norm_attn"], eps)
    q = (x @ _f(a["wq"])).reshape(S, H, hd + hr)
    kv = x @ _f(a["wkv_a"])
    c = _rms(kv[:, :r], a["kv_norm"], eps)
    k_pe = _rope(kv[:, None, r:], pos, theta)                 # (S, 1, hr)
    k_nope = (c @ _f(a["wk_b"])).reshape(S, H, hd)
    v = (c @ _f(a["wv_b"])).reshape(S, H, hv)
    q = jnp.concatenate([q[..., :hd], _rope(q[..., hd:], pos, theta)], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (S, H, hr))], -1)
    s = jnp.einsum("shd,thd->hst", q, k) * (hd + hr) ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hst,thd->shd", jax.nn.softmax(s, -1), v)
    return h + o.reshape(S, H * hv) @ _f(a["wo"])


def _pick(i, tree):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False), tree)


@functools.partial(jax.jit, static_argnames=("z", "eps", "theta"))
def _dense_layer(h, layers, i, *, z, eps, theta):
    lp = _pick(i, layers)
    h = _attention(h, lp, dict(z), eps, theta)
    return h + _swiglu(_rms(h, lp["norm_mlp"], eps), lp["mlp"])


@functools.partial(jax.jit, static_argnames=("z", "eps", "theta", "scale"))
def _moe_layer(h, layers, i, *, z, eps, theta, scale):
    lp = _pick(i, layers)
    z = dict(z)
    h = _attention(h, lp, z, eps, theta)
    x = _rms(h, lp["norm_mlp"], eps)
    m = lp["moe"]
    score = jax.nn.sigmoid(x @ _f(m["wg"]))                   # (S, E)
    _, ids = jax.lax.top_k(score + _f(m["score_bias"]), z["k"])
    w = jnp.take_along_axis(score, ids, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scale        # (S, k)
    held = z["lo"] + jnp.arange(z["n"])
    # each token's weight on each held expert (0 where not picked)
    gate = jnp.sum(jnp.where(ids[:, :, None] == held[None, None, :],
                             w[:, :, None], 0.0), axis=1)     # (S, n)
    g = jnp.einsum("sd,efd->esf", x, _f(m["wi_gate"]))
    u = jnp.einsum("sd,efd->esf", x, _f(m["wi_up"]))
    y = jnp.einsum("esf,efd->esd", jax.nn.silu(g) * u, _f(m["wo"]))
    y = jnp.einsum("esd,se->sd", y, gate)
    return h + y + _swiglu(x, m["shared"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(h, idx, final_norm, unembed, *, eps):
    return _rms(h[idx], final_norm, eps) @ _f(unembed)


def logits_at(params, tokens, score_from: int, cfg: dict) -> np.ndarray:
    """Logits (n, V) at positions ``score_from .. len(tokens) - 1`` of the
    causal forward pass over ``tokens``."""
    S = len(tokens)
    Sp = -(-S // BUCKET) * BUCKET
    toks = np.zeros(Sp, np.int32)
    toks[:S] = tokens
    z = tuple(sorted(_sizes(cfg).items()))
    kw = dict(z=z, eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]))
    with jax.default_matmul_precision("highest"):
        h = _f(params["embed"][jnp.asarray(toks)])
        for i in range(cfg["first_k_dense_replace"]):
            h = _dense_layer(h, params["lead"], i, **kw)
        layers = params["groups"]["pos0"]
        for i in range(cfg["num_hidden_layers"]
                       - cfg["first_k_dense_replace"]):
            h = _moe_layer(h, layers, i,
                           scale=float(cfg["routed_scaling_factor"]), **kw)
        n = S - score_from
        idx = np.minimum(score_from + np.arange(-(-n // 64) * 64), Sp - 1)
        out = _logits(h, jnp.asarray(idx), params["final_norm"],
                      params["unembed"], eps=cfg["rms_norm_eps"])
    return np.asarray(out[:n])
