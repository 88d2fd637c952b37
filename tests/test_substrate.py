"""Substrate: optimizer, data pipeline (determinism/sharding properties),
checkpoint roundtrip + async + retention + resume."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.ckpt import checkpoint as ck
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM
from repro.train import optim


# ---------------------------------------------------------------- optimizer

def test_adamw_converges_on_quadratic():
    target = jnp.asarray([1.5, -2.0, 0.5])
    params = {"w": jnp.zeros(3)}
    opt = optim.init_opt(params)
    cfg = optim.OptConfig(lr=0.1, warmup=5, total_steps=200,
                          weight_decay=0.0)
    loss_fn = lambda p: jnp.sum((p["w"] - target) ** 2)
    for _ in range(200):
        g = jax.grad(loss_fn)(params)
        params, opt, m = optim.adamw_update(g, opt, params, cfg)
    assert loss_fn(params) < 1e-2


def test_grad_clip_bounds_update():
    params = {"w": jnp.zeros(4)}
    opt = optim.init_opt(params)
    cfg = optim.OptConfig(lr=1e-3, clip_norm=1.0, warmup=0, total_steps=10)
    g = {"w": jnp.full(4, 1e6)}
    _, _, m = optim.adamw_update(g, opt, params, cfg)
    assert m["grad_norm"] > 1e5            # reported raw norm


def test_lr_schedule_shape():
    cfg = optim.OptConfig(lr=1.0, warmup=10, total_steps=110)
    lrs = [float(optim.lr_at(cfg, s)) for s in range(110)]
    assert lrs[0] < lrs[9]                  # warmup rises
    assert abs(lrs[10] - 1.0) < 0.02        # peak
    assert lrs[-1] < 0.02                   # cosine decays to ~0


# --------------------------------------------------------------------- data

def test_data_deterministic():
    cfg = DataConfig(vocab_size=101, seq_len=32, global_batch=4, seed=7)
    a = SyntheticLM(cfg).batch(3)
    b = SyntheticLM(cfg).batch(3)
    np.testing.assert_array_equal(a, b)
    c = SyntheticLM(cfg).batch(4)
    assert not np.array_equal(a, c)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 4).map(lambda k: 2 ** k), st.integers(0, 5))
def test_data_host_shards_partition_global_batch(n_hosts, step):
    cfg = DataConfig(vocab_size=97, seq_len=16, global_batch=8 * n_hosts,
                     seed=3)
    full = np.concatenate(
        [SyntheticLM(cfg, host_id=h, n_hosts=n_hosts).batch(step)
         for h in range(n_hosts)])
    ref = SyntheticLM(cfg, host_id=0, n_hosts=1).batch(step)
    np.testing.assert_array_equal(full, ref)   # shards tile the global batch


def test_data_in_vocab_and_learnable():
    cfg = DataConfig(vocab_size=53, seq_len=64, global_batch=8, seed=0)
    b = SyntheticLM(cfg).batch(0)
    assert b.min() >= 0 and b.max() < 53
    # copy motif present: position t % 16 == 0 repeats t-8 for t >= 8
    hits = np.mean([b[i, t] == b[i, t - 8]
                    for i in range(8) for t in range(16, 65, 16)])
    assert hits == 1.0


def test_prefetcher_orders_steps():
    cfg = DataConfig(vocab_size=31, seq_len=8, global_batch=2, seed=1)
    src = SyntheticLM(cfg)
    pf = Prefetcher(lambda s: src.batch(s), start_step=5)
    steps = [next(pf)[0] for _ in range(4)]
    pf.close()
    assert steps == [5, 6, 7, 8]


# --------------------------------------------------------------- checkpoint

def _tree(key=0):
    k = jax.random.PRNGKey(key)
    return {"a": jax.random.normal(k, (8, 16)),
            "nested": {"b": jnp.arange(10, dtype=jnp.int32),
                       "c": (jnp.ones((3,)), jnp.zeros((2, 2)))}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    ck.save(tmp_path / "step_5", t, 5)
    restored, step = ck.restore(tmp_path / "step_5", jax.eval_shape(lambda: t))
    assert step == 5
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_manager_async_retention_resume(tmp_path):
    mgr = ck.CheckpointManager(tmp_path, period=2, keep=2)
    t = _tree()
    for step in range(1, 9):
        t = jax.tree.map(lambda x: x + 1 if x.dtype != jnp.int32 else x, t)
        mgr.maybe_save(t, step)
    mgr.wait()
    assert ck.latest_step(tmp_path) == 8
    kept = sorted(int(p.name.split("_")[-1])
                  for p in pathlib.Path(tmp_path).glob("step_*"))
    assert len(kept) <= 2
    restored, step = mgr.restore_latest(jax.eval_shape(lambda: t))
    assert step == 8
    np.testing.assert_allclose(np.asarray(restored["a"]), np.asarray(t["a"]))


def test_checkpoint_atomicity_overwrite(tmp_path):
    t = _tree(0)
    ck.save(tmp_path / "step_1", t, 1)
    t2 = jax.tree.map(lambda x: x * 2, t)
    ck.save(tmp_path / "step_1", t2, 1)     # overwrite is atomic
    restored, _ = ck.restore(tmp_path / "step_1", jax.eval_shape(lambda: t))
    np.testing.assert_allclose(np.asarray(restored["a"]),
                               np.asarray(t2["a"]))


def test_train_resume_continues(tmp_path):
    """checkpoint/restart: resumed run continues from the saved step."""
    from repro.launch import train as train_mod
    loss1 = train_mod.train(["--arch", "mamba2-780m-smoke", "--steps", "16",
                            "--batch", "4", "--seq", "32",
                            "--ckpt-dir", str(tmp_path), "--ckpt-period",
                            "8"])
    loss2 = train_mod.train(["--arch", "mamba2-780m-smoke", "--steps", "24",
                            "--batch", "4", "--seq", "32",
                            "--ckpt-dir", str(tmp_path), "--resume"])
    assert np.isfinite(loss1) and np.isfinite(loss2)
    assert ck.latest_step(tmp_path) == 24
