"""Train/serve step factories, parameterized by ``ApproxKnobs``.

``make_train_step(cfg, knobs, ...)`` returns a pure function suitable for
``jax.jit`` — one per approximate variant. The Pliant actuator (core/variants)
compiles each variant ONCE and switches which executable runs at a step
boundary: the TPU analogue of DynamoRIO's signal-triggered function swap.

Microbatching (gradient accumulation) runs as a ``lax.scan`` over static
micro-slices; gradients accumulate in fp32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import api
from repro.approx.knobs import ApproxKnobs, PRECISE
from repro.dist import collectives
from repro.train import optim


def grad_reduce_for(knobs: ApproxKnobs, mesh, pspecs=None):
    """The owned gradient-sync region an (knobs, mesh) pair calls for.

    Returns a tree -> tree callable wrapping ONE shard_map region
    (``collectives.grad_sync``), or None when there is nothing to own:

    * single device / mesh without data or pod axes -> None.
    * ``data`` axis present -> explicit in-pod pmean over ``data`` (idempotent
      on GSPMD's implicit reduction, but now trace-visible and priceable).
    * ``pod`` axis present and ``sync_period == 1`` -> the cross-pod mean
      rides in the same region, int8-wire when ``grad_compress == "int8"``.
    * ``sync_period > 1`` -> the pod collective is ELIDED AT TRACE TIME: the
      compiled step carries zero pod wire bytes; the launcher runs
      ``pod_sync`` every k steps instead (local-SGD style).

    The returned callable exposes ``.pod_wire`` / ``.compress`` for
    introspection (tests, dry-run accounting).
    """
    shape = getattr(mesh, "shape", {}) if mesh is not None else {}
    if "data" not in shape and "pod" not in shape:
        return None
    pod_wire = "pod" in shape and knobs.sync_period == 1
    compress = knobs.grad_compress == "int8"

    def reduce_fn(g):
        return collectives.grad_sync(g, mesh, pod_wire=pod_wire,
                                     compress=compress, pspecs=pspecs)
    reduce_fn.pod_wire = pod_wire
    reduce_fn.compress = compress
    return reduce_fn


_POD_SYNC_CACHE = {}


def pod_sync(params, mesh, pspecs=None):
    """Periodic pod-level param sync (the ``sync_period`` knob). No-op
    without a pod axis, so launchers call it unconditionally every k steps.

    Always full-precision wire: int8-compressing the *parameters* would
    re-round model state to 8-bit resolution every sync (unlike gradients,
    where the quantization noise is consumed once and scaled by lr) —
    ``grad_compress`` only shapes the per-step gradient path. The jitted sync
    is cached per (mesh, tree structure) so the train hot loop never
    re-traces it.
    """
    if mesh is None or "pod" not in getattr(mesh, "shape", {}):
        return params
    if pspecs is not None:      # rare, launcher-specific: don't cache
        return collectives.pod_sync_params(params, mesh, pspecs=pspecs)
    key = (mesh, jax.tree.structure(params))
    fn = _POD_SYNC_CACHE.get(key)
    if fn is None:
        fn = jax.jit(lambda p: collectives.pod_sync_params(p, mesh))
        _POD_SYNC_CACHE[key] = fn
    return fn(params)


def _micro_split(batch, n_micro: int):
    def split(x):
        b = x.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        return x.reshape(n_micro, b // n_micro, *x.shape[1:])
    return jax.tree.map(split, batch)


def make_train_step(cfg: ModelConfig, knobs: ApproxKnobs = PRECISE, *,
                    opt_cfg: optim.OptConfig = optim.OptConfig(),
                    n_micro: int = 1, remat: str = "full",
                    ep_axis: Optional[str] = None, mesh=None,
                    donate: bool = True, param_pspecs=None):
    """Returns step(params, opt, batch) -> (params, opt, metrics)."""
    loss_fn = api.loss_fn(cfg)
    grad_reduce = grad_reduce_for(knobs, mesh, param_pspecs)

    def loss_of(params, micro_batch):
        loss, metrics = loss_fn(params, micro_batch, knobs=knobs,
                                ep_axis=ep_axis, mesh=mesh, remat=remat)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    def step(params, opt, batch):
        if n_micro == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            micro = _micro_split(batch, n_micro)

            def body(acc, mb):
                (l, m), g = grad_fn(params, mb)
                acc_g, acc_l = acc
                acc_g = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), acc_g, g)
                return (acc_g, acc_l + l), m

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            from repro import flags
            (gsum, lsum), metrics = jax.lax.scan(
                body, (zero, jnp.zeros((), jnp.float32)), micro,
                unroll=flags.unroll("micro"))
            grads = jax.tree.map(lambda g: g / n_micro, gsum)
            loss = lsum / n_micro
            metrics = jax.tree.map(lambda m: m[-1], metrics)
        params, opt, opt_metrics = optim.adamw_update(grads, opt, params,
                                                      opt_cfg,
                                                      grad_reduce=grad_reduce)
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return params, opt, metrics

    return step


def make_serve_step(cfg: ModelConfig, knobs: ApproxKnobs = PRECISE, *,
                    ep_axis: Optional[str] = None, mesh=None):
    """Returns decode_step(params, tokens, position, caches[, enc_out])
    -> (logits, new_caches). One new token against the KV/SSM caches."""
    decode = api.decode_fn(cfg)

    if cfg.family == "encdec":
        def decode_step(params, tokens, position, caches, enc_out):
            return decode(params, tokens, position, caches, enc_out,
                          knobs=knobs)
        return decode_step

    def decode_step(params, tokens, position, caches):
        return decode(params, tokens, position, caches, knobs=knobs,
                      ep_axis=ep_axis, mesh=mesh)
    return decode_step


def make_paged_serve_step(cfg: ModelConfig, knobs: ApproxKnobs = PRECISE, *,
                          ep_axis: Optional[str] = None, mesh=None,
                          use_kernel: Optional[bool] = None,
                          dynamic_scatter: bool = False,
                          interpret: bool = False):
    """Returns decode_step(params, tokens, position, active, caches)
    -> (logits, new_caches) — one decode step over the paged pool.

    ``active`` (B,) bool masks per-slot cache writes so decode steps can
    interleave with a background admission: the admitting slot's mapped
    pages / SSM rows must not receive garbage from its dead batch row.
    ``use_kernel`` overrides the fused-kernel dispatch; under a ``mesh``
    the kernel runs shard_map'd over the slot-affinity pool layout when
    ``dist.sharding.paged_decode_plan`` allows, else the GSPMD gather path
    (with a logged warning). ``interpret`` runs the sharded kernel in
    Pallas interpret mode (simulated-device CI).
    ``dynamic_scatter`` selects the O(1)-per-entry dynamic cache write
    (single-device pools only — the sharded kernel path does its own
    dynamic write inside the shard; see
    ``attention.paged_decode_attention``)."""
    decode = api.decode_fn(cfg)
    assert cfg.family != "encdec", "paged serving: decoder-only path"

    def decode_step(params, tokens, position, active, caches):
        return decode(params, tokens, position, caches, knobs=knobs,
                      ep_axis=ep_axis, mesh=mesh, active=active,
                      use_kernel=use_kernel, dyn_scatter=dynamic_scatter,
                      interpret=interpret)
    return decode_step


def make_paged_megastep(cfg: ModelConfig, knobs: ApproxKnobs = PRECISE, *,
                        k: int, temperature: float = 0.0, seed: int = 0,
                        eos_id: int = -1, ep_axis: Optional[str] = None,
                        mesh=None, use_kernel: Optional[bool] = None,
                        dynamic_scatter: bool = False,
                        interpret: bool = False):
    """Returns decode_megastep(params, cur, pos, alive, uids, draws, budget,
    caches) -> (toks (B,K), cur, pos, alive, draws, budget, counts, caches)
    — K fused decode steps with on-device sampling and stop masking in ONE
    executable (``lm.decode_megastep``); ``counts`` are their expert
    counters.

    The caches argument sits at position 7 so the engine can jit with
    ``donate_argnums=(7,)`` and update the paged pool + SSM state in
    place. All the per-row carries (cur/pos/alive/draws/budget) round-trip
    through the executable so the engine can chain megasteps device-side
    without a host sync between them."""
    from repro.models import lm as lm_mod
    assert cfg.family != "encdec", "megastep: decoder-only path"

    def decode_megastep(params, cur, pos, alive, uids, draws, budget,
                        caches):
        return lm_mod.decode_megastep(
            params, cur, pos, alive, uids, draws, budget, caches, cfg, knobs,
            k=k, temperature=temperature, seed=seed, eos_id=eos_id,
            ep_axis=ep_axis, mesh=mesh, use_kernel=use_kernel,
            dyn_scatter=dynamic_scatter, interpret=interpret)
    return decode_megastep


def make_paged_admission_step(cfg: ModelConfig, knobs: ApproxKnobs = PRECISE,
                              *, dynamic_scatter: bool = False, mesh=None,
                              use_kernel: Optional[bool] = None,
                              interpret: bool = False):
    """Returns prefill_chunk(params, tokens, start, caches, slot)
    -> (logits, counts, caches), ``counts`` the chunk's expert counters.

    The paged engine's admission cell: one prompt chunk written straight
    into the batched page-pool caches at ``slot``'s block-table row. Both
    ``start`` and ``slot`` are traced, and ``tokens`` may end in pad
    entries (``serve.prefill.PAD_TOKEN``) whose rows write nothing the slot
    reads: where ``serve.prefill.pads_exactly(cfg)``, ONE executable per
    variant, of the full chunk length, serves every chunk of every slot,
    ragged tails included; elsewhere the engine keeps one per (variant,
    chunk length). ``dynamic_scatter`` as in ``make_paged_serve_step``;
    ``mesh``/``use_kernel``/``interpret`` select the ring-sequence-parallel
    chunk attention when the prefill plan applies."""
    from repro.serve import prefill as prefill_mod

    def prefill_chunk(params, tokens, start, caches, slot):
        return prefill_mod.paged_prefill_chunk(params, tokens, start, caches,
                                               slot, cfg, knobs=knobs,
                                               dyn_scatter=dynamic_scatter,
                                               mesh=mesh,
                                               use_kernel=use_kernel,
                                               interpret=interpret)
    return prefill_chunk


def make_prefill_fn(cfg: ModelConfig, knobs: ApproxKnobs = PRECISE, *,
                    ep_axis: Optional[str] = None, mesh=None,
                    remat: str = "full"):
    """Full-sequence forward returning last-token logits (the prefill cell)."""
    from repro.models import encdec as encdec_mod
    from repro.models import lm as lm_mod

    def prefill(params, batch):
        if cfg.family == "encdec":
            enc_out = encdec_mod.encode(params, batch["frames"], cfg, knobs,
                                        remat=remat)
            h = encdec_mod.decode_hidden(params, batch["tokens"][:, :-1],
                                         enc_out, cfg, knobs, remat=remat)
        else:
            h, _ = lm_mod.forward_hidden(
                params, batch["tokens"][:, :-1], cfg, knobs,
                ep_axis=ep_axis, mesh=mesh, remat=remat,
                prefix_embeds=batch.get("prefix_embeds"))
        return lm_mod.logits_fn(params, h[:, -1], cfg)

    return prefill
