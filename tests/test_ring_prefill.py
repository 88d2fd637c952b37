"""The ring-attention sequence-parallel prefill path: on an 8-simulated-device
mesh the ``ServeEngine``'s admission cells must produce token-for-token the
single-device engine's (and the GSPMD unsharded reference's) output with the
ring actually dispatched — plus the dispatch introspection
(``explain_prefill_dispatch``, loud unsharded fallback), the plan's
infeasibility reasons, the per-device cost model, and the flash kernel's
ragged-tail handling the ring path leans on."""
import math

import pytest

from repro.kernels.ring_attention import (prefill_attn_flops,
                                          prefill_hbm_bytes,
                                          sharded_prefill_attn_flops,
                                          sharded_prefill_hbm_bytes)

ARCHS = ["phi4-mini-3.8b-smoke",   # MHA
         "gemma2-27b-smoke",       # GQA + local attention
         "zamba2-2.7b-smoke",      # hybrid attn/SSM
         "mamba2-780m-smoke"]      # pure SSM


def test_ring_engine_token_parity(subproc):
    """Paged admission with ragged chunk boundaries (prompt 10 over chunk 8)
    and a shared prefix, all four architecture families: ring == single
    device == GSPMD unsharded, with the ring counted as the dispatched
    path."""
    out = subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.models import attention as attn_mod
from repro.serve.engine import Request, ServeEngine

def drive(eng, cfg, n_req=6, prompt_len=10, max_new=5, shared=4):
    rng = np.random.default_rng(0)
    base = list(rng.integers(1, cfg.vocab_size, shared))
    reqs = [Request(i, prompt=base + list(
                rng.integers(1, cfg.vocab_size, prompt_len - shared)),
                    max_new=max_new) for i in range(n_req)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return [list(r.out) for r in reqs]

mesh = make_mesh((2, 4), ("data", "model"))
kw = dict(batch_slots=8, max_len=32, page_size=4,
          prefill_chunk=8)
for arch in %r:
    cfg = get_config(arch)
    params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    attn_mod.DISPATCH_COUNTS.clear()
    eng_s = ServeEngine(cfg, params=params, mesh=mesh, use_kernel=True,
                        kernel_interpret=True, **kw)
    assert eng_s.sharded_prefill, arch
    assert "shard_map'd" in eng_s.explain_prefill_dispatch(), \\
        (arch, eng_s.explain_prefill_dispatch())
    out_s = drive(eng_s, cfg)
    counts = dict(attn_mod.DISPATCH_COUNTS)
    has_attn = any(k != "mamba" for k in cfg.pattern)
    if has_attn:
        # the ring IS the dispatched admission path, never the mesh gather
        assert counts.get("ring_prefill", 0) > 0, (arch, counts)
    assert counts.get("prefill_gather_mesh", 0) == 0, (arch, counts)
    eng_1 = ServeEngine(cfg, params=params, use_kernel=True,
                        kernel_interpret=True, **kw)
    out_1 = drive(eng_1, cfg)
    eng_g = ServeEngine(cfg, params=params, mesh=mesh, use_kernel=False,
                        **kw)
    out_g = drive(eng_g, cfg)
    assert out_s == out_1 == out_g, (arch, out_s, out_1, out_g)
    assert all(len(t) == 5 for t in out_s), out_s
    eng_s.pool.assert_consistent()
    print("PARITY_OK", arch)
print("ALL_OK")
""" % ARCHS, devices=8)
    assert "ALL_OK" in out
    for arch in ARCHS:
        assert f"PARITY_OK {arch}" in out


def test_ring_dense_engine_token_parity(subproc):
    """Four slots over the 2x4 mesh, unshared 10-token prompts on the
    local+global and hybrid archs: admission dispatches the ring, and the
    tokens equal the single-device engine's."""
    out = subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.models import attention as attn_mod
from repro.serve.engine import Request, ServeEngine

def drive(eng, cfg):
    rng = np.random.default_rng(1)
    reqs = [Request(i, prompt=list(rng.integers(1, cfg.vocab_size, 10)),
                    max_new=4) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return [list(r.out) for r in reqs]

mesh = make_mesh((2, 4), ("data", "model"))
kw = dict(batch_slots=4, max_len=32, prefill_chunk=8)
for arch in ("gemma2-27b-smoke", "zamba2-2.7b-smoke"):
    cfg = get_config(arch)
    params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    attn_mod.DISPATCH_COUNTS.clear()
    eng_s = ServeEngine(cfg, params=params, mesh=mesh, use_kernel=True,
                        kernel_interpret=True, **kw)
    assert eng_s.sharded_prefill, arch
    out_s = drive(eng_s, cfg)
    assert attn_mod.DISPATCH_COUNTS.get("ring_prefill", 0) > 0, \\
        dict(attn_mod.DISPATCH_COUNTS)
    eng_1 = ServeEngine(cfg, params=params, **kw)
    out_1 = drive(eng_1, cfg)
    assert out_s == out_1, (arch, out_s, out_1)
    print("DENSE_OK", arch)
print("ALL_OK")
""", devices=8)
    assert "ALL_OK" in out


def test_prefill_fallback_is_loud(subproc):
    out = subproc("""
import sys
sys.stderr = sys.stdout          # capture the fallback warning
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.models import attention as attn_mod
from repro.serve.engine import Request, ServeEngine

cfg = get_config("gemma2-27b-smoke")
params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
mesh = make_mesh((2, 4), ("data", "model"))
attn_mod.DISPATCH_COUNTS.clear()
# kernel explicitly off under a mesh -> unsharded admission + one-line warn
eng = ServeEngine(cfg, batch_slots=8, max_len=32, params=params, mesh=mesh,
                  page_size=4, prefill_chunk=8, use_kernel=False)
assert not eng.sharded_prefill
assert "unsharded" in eng.explain_prefill_dispatch(), \\
    eng.explain_prefill_dispatch()
r = Request(0, prompt=list(np.arange(1, 11)), max_new=3)
eng.submit(r)
eng.run()
assert len(r.out) == 3
assert attn_mod.DISPATCH_COUNTS.get("prefill_gather_mesh", 0) > 0, \\
    dict(attn_mod.DISPATCH_COUNTS)
assert attn_mod.DISPATCH_COUNTS.get("ring_prefill", 0) == 0
print("FALLBACK_OK")
""", devices=8)
    assert "FALLBACK_OK" in out
    assert "GSPMD unsharded path" in out   # the loud one-liner fired


def test_ring_numerics_direct(subproc):
    """ring_chunk_attention vs a masked-softmax oracle on raw arrays:
    position holes, causal striping, window mode, softcap, int8 KV."""
    out = subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.dist.sharding import prefill_plan
from repro.kernels.ring_attention import ring_chunk_attention
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_config("gemma2-27b-smoke")
plan, reason = prefill_plan(cfg, mesh, 10)
assert plan is not None, reason
assert plan.n_shards == 2 and plan.seq_axis == "data", vars(plan)

B, C, G, R, hd, L = 1, 10, 2, 2, 16, 42
rng = np.random.default_rng(0)
q = jnp.asarray(rng.normal(size=(B, C, G, R, hd)) * 0.3, jnp.float32)
q_pos = jnp.asarray(np.broadcast_to(np.arange(32, 32 + C), (B, C)),
                    jnp.int32)
kv_pos = np.broadcast_to(np.arange(L), (B, L)).copy()
kv_pos[:, 5:9] = -1                      # unmapped hole
kv_pos = jnp.asarray(kv_pos, jnp.int32)

def ref(q, k, v, qp, kvp, window, cap, kv_scale):
    dq = (lambda a: a.astype(jnp.float32) * kv_scale) if kv_scale else \\
        (lambda a: a.astype(jnp.float32))
    s = jnp.einsum("bcgrd,blgd->bgrcl", q, dq(k)) * hd ** -0.5
    if cap:
        s = cap * jnp.tanh(s / cap)
    qe, ke = qp[:, None, None, :, None], kvp[:, None, None, None, :]
    mask = (ke >= 0) & (ke <= qe)
    if window:
        mask &= ke > qe - window
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bgrcl,blgd->bcgrd", p, dq(v))

for window in (0, 8):
    for cap in (0.0, 30.0):
        k = jnp.asarray(rng.normal(size=(B, L, G, hd)) * 0.3, jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, L, G, hd)), jnp.float32)
        o = ring_chunk_attention(q, k, v, q_pos, kv_pos, mesh=mesh,
                                 plan=plan, window=window, cap=cap,
                                 interpret=True)
        want = ref(q, k, v, q_pos, kv_pos, window, cap, 0.0)
        err = float(jnp.max(jnp.abs(o - want)))
        assert err < 1e-5, (window, cap, err)
ki = jnp.asarray(rng.integers(-127, 128, (B, L, G, hd)), jnp.int8)
vi = jnp.asarray(rng.integers(-127, 128, (B, L, G, hd)), jnp.int8)
o = ring_chunk_attention(q, ki, vi, q_pos, kv_pos, mesh=mesh, plan=plan,
                         kv_scale=0.05, interpret=True)
want = ref(q, ki, vi, q_pos, kv_pos, 0, 0.0, 0.05)
err = float(jnp.max(jnp.abs(o - want)))
assert err < 1e-5, err
print("NUMERICS_OK")
""", devices=8)
    assert "NUMERICS_OK" in out


def test_explain_prefill_dispatch_single_device():
    from repro.configs import get_config
    from repro.models.attention import explain_prefill_dispatch

    cfg = get_config("gemma2-27b-smoke")
    s = explain_prefill_dispatch(cfg, None, chunk_len=16, use_kernel=True)
    assert "single device" in s
    s = explain_prefill_dispatch(cfg, None, chunk_len=16, use_kernel=False)
    assert "single device" in s


def test_prefill_plan_infeasible_reasons():
    """prefill_plan explains WHY it falls back (surfaced in the warning and
    the startup banner)."""
    from repro.configs import get_config
    from repro.dist.sharding import prefill_plan

    cfg = get_config("gemma2-27b-smoke")
    plan, reason = prefill_plan(cfg, None, 16)
    assert plan is None and "single device" in reason

    class FakeMesh:
        shape = {"model": 4}
    plan, reason = prefill_plan(cfg, FakeMesh(), 16)
    assert plan is None and "batch mesh axis" in reason

    class WideMesh:
        shape = {"data": 64}
    plan, reason = prefill_plan(cfg, WideMesh(), 16)
    assert plan is None and "chunk_len" in reason


def test_prefill_per_device_work_scales():
    """The acceptance account: per-device ring FLOPs and HBM bytes at the
    32k target shape are ~1/n_shards of the unsharded chunk's."""
    C, L, H, G, hd = 2048, 32768, 16, 8, 128
    total_f = prefill_attn_flops(C, L, H, hd)
    total_b = prefill_hbm_bytes(C, L, G, hd, n_heads=H)
    for n in (2, 4, 8):
        per_f = sharded_prefill_attn_flops(C, L, H, hd, n_shards=n)
        per_b = sharded_prefill_hbm_bytes(C, L, G, hd, n_shards=n,
                                          n_heads=H)
        assert 0.8 * n <= total_f / per_f <= n, (n, total_f / per_f)
        assert 0.8 * n <= total_b / per_b <= n, (n, total_b / per_b)


def test_sharded_prefill_bytes_match_per_shard_account():
    """sharded bytes == the single-device model applied to one shard's
    resident queries and initial K/V shard — the definition the kernel
    bench persists."""
    C, L, G, hd, H, n = 100, 1000, 4, 64, 8, 8
    got = sharded_prefill_hbm_bytes(C, L, G, hd, n_shards=n, n_heads=H)
    want = prefill_hbm_bytes(math.ceil(C / n), math.ceil(L / n), G, hd,
                             n_heads=H)
    assert got == want


def test_flash_attention_ragged_tail():
    """Satellite: chunk lengths that are not block-size multiples are padded
    and masked, not silently miscomputed."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention

    B, H, KVH, hd = 1, 4, 2, 32
    key = jax.random.PRNGKey(0)
    for Sq, Skv in ((100, 100), (130, 130), (37, 64)):
        kq, kk, kv = jax.random.split(jax.random.fold_in(key, Sq), 3)
        q = jax.random.normal(kq, (B, H, Sq, hd)) * 0.3
        k = jax.random.normal(kk, (B, KVH, Skv, hd)) * 0.3
        v = jax.random.normal(kv, (B, KVH, Skv, hd))
        got = flash_attention(q, k, v, causal=False, interpret=True,
                              bq=64, bk=64)
        want = ref.mha_ref(q, k, v, causal=False)
        err = float(jnp.max(jnp.abs(got - want)))
        assert err < 1e-5, (Sq, Skv, err)
    # causal + window on a ragged length (equal Sq/Skv: flash's causal mask
    # is prefill-anchored at position 0, unlike mha_ref's decode alignment)
    kq, kk, kv = jax.random.split(jax.random.fold_in(key, 99), 3)
    q = jax.random.normal(kq, (B, H, 100, hd)) * 0.3
    k = jax.random.normal(kk, (B, KVH, 100, hd)) * 0.3
    v = jax.random.normal(kv, (B, KVH, 100, hd))
    got = flash_attention(q, k, v, causal=True, window=16, interpret=True,
                          bq=64, bk=64)
    want = ref.mha_ref(q, k, v, causal=True, window=16)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
