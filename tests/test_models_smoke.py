"""Per-arch REDUCED-config smoke tests (required deliverable): instantiate
each family at toy scale, run one forward/train step on CPU, assert output
shapes and no NaNs; plus decode-vs-full equivalence per cache type."""
import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.approx.knobs import ApproxKnobs, PRECISE
from repro.configs import ARCHS, get_config
from repro.models import api, encdec, lm
from repro.train import optim, step as step_mod

ALL = list(ARCHS)


def _batch(cfg, B=2, S=32, key=1):
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(key),
                                          (B, S + 1), 0, cfg.vocab_size)}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.encoder_seq, cfg.d_model))
    if cfg.family == "vlm":
        batch["prefix_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.n_prefix_tokens, cfg.d_model))
    return batch


@pytest.mark.parametrize("name", ALL)
def test_forward_and_train_step(name):
    cfg = get_config(name + "-smoke")
    params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    opt = optim.init_opt(params)
    batch = _batch(cfg)
    step = jax.jit(step_mod.make_train_step(
        cfg, opt_cfg=optim.OptConfig(lr=1e-3, warmup=2, total_steps=10),
        remat="none"))
    params2, opt2, metrics = step(params, opt, batch)
    assert jnp.isfinite(metrics["loss"]), name
    assert jnp.isfinite(metrics["grad_norm"]) and metrics["grad_norm"] > 0
    # shapes preserved, params actually moved
    moved = 0.0
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
        assert a.shape == b.shape and a.dtype == b.dtype
        moved += float(jnp.sum(jnp.abs(a - b)))
    assert moved > 0
    assert int(opt2.step) == 1


@pytest.mark.parametrize("name", ["gemma3-12b", "zamba2-2.7b",
                                  "moonlight-16b-a3b"])
def test_approx_variant_step(name):
    cfg = get_config(name + "-smoke")
    params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    opt = optim.init_opt(params)
    knobs = ApproxKnobs(matmul_precision="int8", token_drop=0.5,
                        layer_skip=0.5,
                        topk_override=1 if cfg.moe else 0)
    step = jax.jit(step_mod.make_train_step(cfg, knobs, remat="none"))
    _, _, metrics = step(params, opt, _batch(cfg))
    assert jnp.isfinite(metrics["loss"])


@pytest.mark.parametrize("name", ["gemma3-12b", "whisper-large-v3",
                                  "zamba2-2.7b"])
def test_decode_matches_full_forward(name):
    cfg = get_config(name + "-smoke")
    params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    if cfg.family == "encdec":
        frames = jax.random.normal(jax.random.PRNGKey(2),
                                   (B, cfg.encoder_seq, cfg.d_model))
        enc = encdec.encode(params, frames, cfg, remat="none")
        h = encdec.decode_hidden(params, toks, enc, cfg, remat="none")
        want = lm.logits_fn(params, h[:, -1], cfg)
        caches = encdec.init_caches(cfg, B, S, dtype=jnp.float32)
        for i in range(S):
            got, caches = encdec.encdec_decode_step(
                params, toks[:, i:i+1], jnp.full((B,), i, jnp.int32),
                caches, enc, cfg)
    else:
        h, _ = lm.forward_hidden(params, toks, cfg, remat="none")
        want = lm.logits_fn(params, h[:, -1], cfg)
        caches = lm.init_caches(cfg, B, S, dtype=jnp.float32)
        step = jax.jit(lambda p, t, pos, c: lm.decode_step(p, t, pos, c, cfg))
        for i in range(S):
            got, caches = step(params, toks[:, i:i+1],
                               jnp.full((B,), i, jnp.int32), caches)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


def test_microbatch_equals_full_batch_grads():
    cfg = get_config("phi4-mini-3.8b-smoke")
    params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    opt = optim.init_opt(params)
    batch = _batch(cfg, B=4)
    s1 = jax.jit(step_mod.make_train_step(cfg, remat="none", n_micro=1))
    s2 = jax.jit(step_mod.make_train_step(cfg, remat="none", n_micro=2))
    p1, _, m1 = s1(params, opt, batch)
    p2, _, m2 = s2(params, opt, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-5)


def test_remat_policies_equal_loss():
    cfg = get_config("mistral-large-123b-smoke")   # 2-level factorable groups
    params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    batch = _batch(cfg)
    lf = api.loss_fn(cfg)
    vals = []
    for remat in ["none", "full", "2level"]:
        loss, _ = jax.jit(lambda p, b, r=remat: lf(p, b, knobs=PRECISE,
                                                   remat=r))(params, batch)
        vals.append(float(loss))
    np.testing.assert_allclose(vals[0], vals[1], rtol=1e-5)
    np.testing.assert_allclose(vals[0], vals[2], rtol=1e-5)


def test_moe_capacity_drops_tokens_but_stays_finite():
    cfg = get_config("olmoe-1b-7b-smoke")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.25))
    params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    lf = api.loss_fn(cfg)
    loss, _ = jax.jit(lambda p, b: lf(p, b, knobs=PRECISE, remat="none"))(
        params, _batch(cfg))
    assert jnp.isfinite(loss)
