"""Decoder-only LM (all 10 archs route through here; whisper adds an encoder
in ``encdec.py``). Layers are scanned in *groups* — one group = one period of
``cfg.pattern`` — so HLO size is independent of depth. Zamba2's shared
attention block lives outside the scanned stack and is closed over (weights
reused every invocation, gradients accumulate through the scan).

Cross-entropy is computed in sequence chunks under ``jax.checkpoint`` so the
(B,S,V) logit tensor never materializes — required for 256k-vocab archs.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (ATTN, LOCAL_ATTN, MAMBA, SHARED_ATTN,
                                ModelConfig)
from repro.models.common import (ParamSpec, init_params, rms_norm, softcap,
                                 stack_specs)
from repro.models import attention as attn_mod
from repro.models import mamba2 as mamba_mod
from repro.models.blocks import block_decode, block_forward, block_specs
from repro.approx.knobs import ApproxKnobs, PRECISE, keep_groups
from repro.dist.annotate import constrain_batch, constrain_vocab


# ------------------------------------------------------------------ specs --

def lm_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    specs: Dict[str, Any] = {
        "embed": ParamSpec((cfg.vocab_size, d), ("vocab", "embed")),
        "final_norm": ParamSpec((d,), ("embed",), init="ones"),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = ParamSpec((d, cfg.vocab_size), ("embed", "vocab"))
    if cfg.n_dense_layers:
        specs["lead"] = stack_specs(block_specs(ATTN, cfg, dense=True),
                                    cfg.n_dense_layers)
    groups: Dict[str, Any] = {}
    for j, kind in enumerate(cfg.pattern):
        if kind == SHARED_ATTN:
            continue
        groups[f"pos{j}"] = stack_specs(block_specs(kind, cfg), cfg.n_groups)
    specs["groups"] = groups
    if SHARED_ATTN in cfg.pattern:
        specs["shared"] = block_specs(ATTN, cfg)
    return specs


def init_lm(cfg: ModelConfig, key, dtype=jnp.bfloat16):
    return init_params(lm_specs(cfg), key, dtype)


def _slice_groups(groups, keep: Tuple[int, ...], n_groups: int):
    if len(keep) == n_groups:
        return groups
    idx = np.asarray(keep)
    return jax.tree.map(lambda p: p[idx], groups)


# ---------------------------------------------------------------- forward --

def forward_hidden(params, tokens, cfg: ModelConfig,
                   knobs: ApproxKnobs = PRECISE, *,
                   ep_axis: Optional[str] = None, mesh=None,
                   prefix_embeds: Optional[jax.Array] = None,
                   remat: str = "full"):
    """tokens: (B, S_text) -> (h (B,S,D) final-normed, aux loss).

    ``prefix_embeds``: (B, P, D) stub modality embeddings prepended (vlm).
    """
    h = params["embed"][tokens]
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    h = constrain_batch(h)
    B, S, D = h.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    for i in range(cfg.n_dense_layers):
        lp = jax.tree.map(lambda p: p[i], params["lead"])
        h, _ = block_forward(ATTN, lp, h, positions, cfg, knobs,
                             ep_axis=ep_axis, mesh=mesh)
    keep = keep_groups(cfg.n_groups, knobs.layer_skip)
    groups = _slice_groups(params["groups"], keep, cfg.n_groups)
    shared = params.get("shared")

    def group_body(carry, group_params):
        h, aux = carry
        for j, kind in enumerate(cfg.pattern):
            p = shared if kind == SHARED_ATTN else group_params[f"pos{j}"]
            h, a = block_forward(kind, p, h, positions, cfg, knobs,
                                 ep_axis=ep_axis, mesh=mesh)
            aux = aux + a
        return (constrain_batch(h), aux), None

    from repro import flags
    carry0 = (h, jnp.zeros((), jnp.float32))
    if remat == "2level":
        # sqrt-depth activation memory: nested checkpointed scans store
        # ~(no + ni) layer boundaries instead of G (needed for 88-layer archs)
        no, ni = _near_sqrt_factors(len(keep))
        if no > 1:
            g2 = jax.tree.map(
                lambda p: p.reshape(no, ni, *p.shape[1:]), groups)

            def outer_body(carry, gp_outer):
                c, _ = jax.lax.scan(jax.checkpoint(group_body), carry,
                                    gp_outer, unroll=flags.unroll("groups"))
                return c, None

            (h, aux), _ = jax.lax.scan(
                jax.checkpoint(outer_body), carry0, g2,
                unroll=flags.unroll("groups_outer"))
            return rms_norm(h, params["final_norm"], cfg.norm_eps), aux
        remat = "full"                      # prime group count: fall back
    if remat == "full":
        group_body = jax.checkpoint(group_body)
    elif remat == "dots":
        group_body = jax.checkpoint(
            group_body,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    (h, aux), _ = jax.lax.scan(group_body, carry0, groups,
                               unroll=flags.unroll("groups"))
    return rms_norm(h, params["final_norm"], cfg.norm_eps), aux


def _near_sqrt_factors(g: int):
    """(no, ni) with no*ni == g, no as close to sqrt(g) as possible."""
    best = (1, g)
    for no in range(2, int(g ** 0.5) + 1):
        if g % no == 0:
            best = (no, g // no)
    return best


def _unembed(params, cfg: ModelConfig):
    if "unembed" in params:
        return params["unembed"]
    return params["embed"].T


def logits_fn(params, h, cfg: ModelConfig):
    """h: (..., D) -> (..., V), softcapped. Small inputs only (decode)."""
    logits = (h @ _unembed(params, cfg)).astype(jnp.float32)
    return softcap(logits, cfg.final_softcap)


def ce_chunk(s: int, target: int = 512) -> int:
    """Largest divisor of ``s`` that is <= target (CE chunk length)."""
    c = min(target, s)
    while s % c:
        c -= 1
    return c


def chunked_xent(params, h, labels, mask, cfg: ModelConfig, *,
                 chunk: int = 512):
    """Mean next-token CE without materializing full logits.

    h: (B,S,D); labels: (B,S) (already shifted); mask: (B,S) float weights.
    """
    B, S, D = h.shape
    C = ce_chunk(S, chunk)
    nc = S // C
    emb = _unembed(params, cfg)
    h = constrain_batch(h)
    hs = h.reshape(B, nc, C, D).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, nc, C).transpose(1, 0, 2)
    ms = mask.reshape(B, nc, C).transpose(1, 0, 2)

    @jax.checkpoint
    def body(carry, xs):
        hc, lc, mc = xs
        hc = constrain_batch(hc)          # (B, C, D): keep batch sharded
        logits = (hc @ emb).astype(jnp.float32)
        logits = constrain_vocab(logits)  # (B, C, V): vocab stays sharded
        logits = softcap(logits, cfg.final_softcap)
        # gather-free gold logit: take_along_axis over a sharded vocab dim
        # forces GSPMD to replicate the whole logits matmul (21x FLOPs,
        # EXPERIMENTS.md §Perf); a one-hot contraction keeps vocab sharded.
        m = jnp.max(logits, axis=-1, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
        onehot = jax.nn.one_hot(lc, logits.shape[-1], dtype=logits.dtype)
        gold = jnp.einsum("bcv,bcv->bc", logits, constrain_vocab(onehot))
        loss_sum, w_sum = carry
        return (loss_sum + jnp.sum((lse - gold) * mc),
                w_sum + jnp.sum(mc)), None

    from repro import flags
    (loss_sum, w_sum), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hs, ls, ms), unroll=flags.unroll("ce"))
    return loss_sum / jnp.maximum(w_sum, 1.0)


def lm_loss(params, batch, cfg: ModelConfig, knobs: ApproxKnobs = PRECISE, *,
            ep_axis: Optional[str] = None, mesh=None, remat: str = "full",
            aux_coef: float = 0.01):
    """batch: {"tokens": (B,S+1) int32, optional "prefix_embeds"}."""
    tokens = batch["tokens"]
    if knobs.token_drop > 0:                       # batch perforation
        b_keep = max(1, int(tokens.shape[0] * (1.0 - knobs.token_drop)))
        tokens = tokens[:b_keep]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    prefix = batch.get("prefix_embeds")
    if prefix is not None and knobs.token_drop > 0:
        prefix = prefix[: tokens.shape[0]]
    h, aux = forward_hidden(params, inputs, cfg, knobs, ep_axis=ep_axis,
                            mesh=mesh, prefix_embeds=prefix, remat=remat)
    if prefix is not None:
        P = prefix.shape[1]
        # prefix positions predict nothing; text position i predicts label i
        h = h[:, P:]
    mask = jnp.ones_like(labels, jnp.float32)
    loss = chunked_xent(params, h, labels, mask, cfg)
    return loss + aux_coef * aux, {"ce": loss, "aux": aux}


# ----------------------------------------------------------------- decode --

def _stacked_caches(cfg: ModelConfig, one):
    """One cache entry per pattern position, stacked over the groups, then
    (where the model has leading dense layers) theirs, stacked over them."""
    def stack(tree, n):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)
    caches = tuple(stack(one(kind), cfg.n_groups) for kind in cfg.pattern)
    if cfg.n_dense_layers:
        caches += (stack(one(ATTN), cfg.n_dense_layers),)
    return caches


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16, quantized: bool = False):
    """Stacked (over groups) caches, one entry per pattern position."""
    if cfg.mla:
        raise NotImplementedError("latent (MLA) attention is served from "
                                  "the paged latent pool")

    def one(kind):
        if kind == MAMBA:
            return mamba_mod.init_mamba_cache(cfg, batch, dtype)
        length = min(cfg.window, max_len) if kind == LOCAL_ATTN else max_len
        return attn_mod.init_cache(cfg, batch, length, dtype,
                                   quantized=quantized)
    return _stacked_caches(cfg, one)


def init_paged_caches(cfg: ModelConfig, batch: int, n_pages: int,
                      page_size: int, max_pages: int, dtype=jnp.bfloat16,
                      quantized: bool = False):
    """Paged sibling of ``init_caches``: attention positions get a
    ``PagedKVCache`` over a shared physical page pool + per-slot block
    tables (``serve.pages`` owns allocation); Mamba state stays per-slot.
    Local-attention layers share the same full-length block tables and mask
    by window at attention time — pages beyond the window are dead weight a
    smarter allocator could free, but the mapping stays uniform.

    Every leaf is group-stacked (axis 0 = layer groups) like ``init_caches``
    so the decode scan consumes it unchanged; the block table is replicated
    per group (a few KiB) to keep the pytree scan-uniform. Latent (MLA)
    models get a ``PagedLatentCache``: one [latent | rotary key] entry per
    token and layer in place of K and V per head.
    """
    def one(kind):
        if kind == MAMBA:
            return mamba_mod.init_mamba_cache(cfg, batch, dtype)
        init = (attn_mod.init_paged_latent_cache if cfg.mla
                else attn_mod.init_paged_cache)
        return init(cfg, batch, n_pages, page_size, max_pages, dtype,
                    quantized=quantized)
    return _stacked_caches(cfg, one)


def decode_step(params, tokens, position, caches, cfg: ModelConfig,
                knobs: ApproxKnobs = PRECISE, *,
                ep_axis: Optional[str] = None, mesh=None,
                enc_out: Optional[jax.Array] = None, active=None,
                use_kernel: Optional[bool] = None,
                dyn_scatter: bool = False, interpret: bool = False):
    """tokens: (B,1) int32; position: (B,) absolute positions.

    Returns (logits (B,V) fp32, new_caches). ``active`` (B,) bool masks
    per-slot cache writes; ``use_kernel`` overrides the paged-attention
    kernel dispatch and ``dyn_scatter`` the paged cache-write form (see
    ``blocks.block_decode``). All hybrid layer kinds (attention pages AND
    Mamba state rows) advance inside the ONE ``lax.scan`` body below, so a
    mixed block stack is a single lowered executable per decode step.
    """
    logits, new_caches, _ = decode_counted(
        params, tokens, position, caches, cfg, knobs, ep_axis=ep_axis,
        mesh=mesh, enc_out=enc_out, active=active, use_kernel=use_kernel,
        dyn_scatter=dyn_scatter, interpret=interpret)
    return logits, new_caches


def decode_counted(params, tokens, position, caches, cfg: ModelConfig,
                   knobs: ApproxKnobs = PRECISE, *,
                   ep_axis: Optional[str] = None, mesh=None,
                   enc_out: Optional[jax.Array] = None, active=None,
                   use_kernel: Optional[bool] = None,
                   dyn_scatter: bool = False, interpret: bool = False):
    """``decode_step`` that also returns the step's expert counters:
    (logits, new_caches, counts (2,) int32 summed over layers)."""
    from repro import flags
    h = params["embed"][tokens[:, 0]][:, None, :]
    kw = dict(ep_axis=ep_axis, mesh=mesh, enc_out=enc_out, active=active,
              use_kernel=use_kernel, dyn_scatter=dyn_scatter,
              interpret=interpret)

    def layer(kind, p, h, cache, i):
        return block_decode(kind, p, h, position, cache, cfg, knobs, layer=i,
                            **kw)

    h, new_caches, counts = scan_layers(params, caches, cfg, h, layer,
                                        unroll=flags.unroll("groups"))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return logits_fn(params, h[:, 0], cfg), new_caches, counts


_EXPERT_MATS = ("wi_gate", "wi_up", "wo")


def scan_layers(params, caches, cfg: ModelConfig, h, layer_fn, *,
                unroll=1):
    """Run the leading dense layers, then the scanned groups, threading the
    hidden state through ``layer_fn(kind, layer_params, h, cache, i)`` ->
    (h, new_cache, counts (2,) int32) over ``caches`` (the
    ``init_caches`` / ``init_paged_caches`` layout). Returns (h,
    new_caches, counts summed over layers).

    Paged pools (``PagedKVCache``, ``PagedLatentCache``) and the expert
    matrices stay stacked over the layers: the scan carries the former
    (written in place) and closes over the latter, and ``layer_fn`` gets
    them whole with the layer index ``i``, so a kernel reads its layer
    without the per-layer copy a scanned slice would cost, and no layer's
    pool is written back into a second stack. Every other cache (per-slot
    Mamba state, ring ``KVCache``) and parameter is sliced per group."""
    from repro.models.attention import PagedKVCache, PagedLatentCache
    n_pat = len(cfg.pattern)
    shared = params.get("shared")
    counts = jnp.zeros((2,), jnp.int32)

    def run(h, counts, stack, stack_caches, kinds, n):
        pooled = [isinstance(c, (PagedKVCache, PagedLatentCache))
                  for c in stack_caches]
        xs_params, mats = {}, {}
        for key, p in stack.items():
            if "moe" in p:
                moe = dict(p["moe"])
                mats[key] = {k: moe.pop(k) for k in _EXPERT_MATS}
                p = dict(p, moe=moe)
            xs_params[key] = p

        def body(carry, xs):
            h, counts, carried = carry
            gp, gc, i = xs
            carried, ys = list(carried), []
            for j, kind in enumerate(kinds):
                key = f"pos{j}"
                p = shared if kind == SHARED_ATTN else gp.get(key)
                if key in mats:
                    p = dict(p, moe=dict(p["moe"], **mats[key]))
                h, nc, c = layer_fn(kind, p, h,
                                    carried[j] if pooled[j] else gc[j], i)
                counts = counts + c
                if pooled[j]:
                    carried[j], nc = nc, None
                ys.append(nc)
            return (h, counts, tuple(carried)), tuple(ys)

        carried = tuple(c if l else None
                        for c, l in zip(stack_caches, pooled))
        xs_c = tuple(None if l else c for c, l in zip(stack_caches, pooled))
        (h, counts, carried), ys = jax.lax.scan(
            body, (h, counts, carried), (xs_params, xs_c, jnp.arange(n)),
            unroll=unroll)
        return h, counts, tuple(c if l else y
                                for c, y, l in zip(carried, ys, pooled))

    lead = ()
    if cfg.n_dense_layers:
        h, counts, lead = run(h, counts, {"pos0": params["lead"]},
                              (caches[n_pat],), (ATTN,), cfg.n_dense_layers)
    h, counts, groups = run(h, counts, params["groups"], caches[:n_pat],
                            cfg.pattern, cfg.n_groups)
    return h, groups + lead, counts


def sample_token(logits, uids, draws, *, temperature: float = 0.0,
                 seed: int = 0):
    """On-device sampler under the ``(seed, uid, draw_index)`` contract.

    logits: (B, V) fp32; uids/draws: (B,) int32. Greedy argmax when
    ``temperature <= 0``; otherwise a gumbel-max categorical draw keyed by
    ``fold_in(fold_in(PRNGKey(seed), uid), draw)`` — the key depends only on
    the request identity and how many tokens it has emitted, NOT on batch
    slot, megastep width, or dispatch grouping, so any decode schedule that
    respects sequential draw indices produces the same stream.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    base = jax.random.PRNGKey(seed)

    def one(row, uid, draw):
        k = jax.random.fold_in(jax.random.fold_in(base, uid), draw)
        return jax.random.categorical(k, row / temperature)

    return jax.vmap(one)(logits, uids, draws).astype(jnp.int32)


def decode_megastep(params, cur, pos, alive, uids, draws, budget, caches,
                    cfg: ModelConfig, knobs: ApproxKnobs = PRECISE, *,
                    k: int, temperature: float = 0.0, seed: int = 0,
                    eos_id: int = -1, ep_axis: Optional[str] = None,
                    mesh=None, use_kernel: Optional[bool] = None,
                    dyn_scatter: bool = False, interpret: bool = False):
    """K fused decode steps in one executable: a ``lax.scan`` whose body IS
    ``decode_step`` plus on-device sampling and stop masking — the host
    learns K tokens per row from a single transfer.

    cur: (B,) int32 current tokens (the token whose KV gets written at
    ``pos``); pos: (B,) int32 absolute positions; alive: (B,) bool live-row
    mask (doubles as ``decode_step``'s cache-write ``active``); uids/draws:
    (B,) int32 sampler-stream coordinates; budget: (B,) int32 tokens each
    row may still emit (``max_new - len(out)`` on host).

    Per scan iteration a live row writes KV at ``pos``, samples the next
    token, and advances; a dead row is frozen — its carry is untouched and
    its output slot carries the -1 sentinel (vocab ids are >= 0). Rows die
    in-scan on EOS (when ``eos_id >= 0``) or on budget exhaustion, so an
    EOS landing mid-megastep stops that row's cache writes immediately
    without disturbing siblings. Max KV write position over the scan is
    ``pos + k - 1`` on a fully-live row — the host pre-reserves that page
    range (``PagePool.ensure_decode_range``) before dispatch.

    Returns ``(toks (B, K) int32, cur, pos, alive, draws, budget, counts,
    new_caches)``: ``counts`` (2,) int32 sums the expert counters of the K
    steps (``blocks._serve_ffn``).
    """

    def body(carry, _):
        cur, pos, alive, draws, budget, counts, caches = carry
        logits, caches, c = decode_counted(
            params, cur[:, None], pos, caches, cfg, knobs, ep_axis=ep_axis,
            mesh=mesh, active=alive, use_kernel=use_kernel,
            dyn_scatter=dyn_scatter, interpret=interpret)
        tok = sample_token(logits, uids, draws, temperature=temperature,
                           seed=seed)
        emit = alive
        out = jnp.where(emit, tok, jnp.int32(-1))
        step1 = emit.astype(jnp.int32)
        draws = draws + step1
        budget = budget - step1
        hit_eos = (out == jnp.int32(eos_id)) if eos_id >= 0 else \
            jnp.zeros_like(alive)
        alive = alive & ~hit_eos & (budget > 0)
        pos = pos + step1
        cur = jnp.where(emit, tok, cur)
        return (cur, pos, alive, draws, budget, counts + c, caches), out

    carry0 = (cur, pos, alive, draws, budget, jnp.zeros((2,), jnp.int32),
              caches)
    (cur, pos, alive, draws, budget, counts, caches), toks = jax.lax.scan(
        body, carry0, None, length=k)
    return toks.T, cur, pos, alive, draws, budget, counts, caches
