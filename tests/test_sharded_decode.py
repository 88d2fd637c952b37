"""The sharded fused decode path: on an 8-simulated-device mesh the paged
``ServeEngine`` must produce token-for-token the single-device kernel's (and
the gather reference's) output with the shard_map'd fused kernel actually
dispatched — plus the dispatch introspection (``explain_dispatch``, loud
gather fallback) and the per-device HBM bytes account."""
import pytest

from repro.kernels.paged_attention import (decode_hbm_bytes,
                                           sharded_decode_hbm_bytes)

ARCHS = ["phi4-mini-3.8b-smoke",   # MHA
         "gemma2-27b-smoke",       # GQA + local attention
         "zamba2-2.7b-smoke",      # hybrid attn/SSM
         "mamba2-780m-smoke"]      # pure SSM


def test_sharded_engine_token_parity(subproc):
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
for arch in %r:
    cfg = get_config(arch)
    params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    attn_mod.DISPATCH_COUNTS.clear()
    eng_s = ServeEngine(cfg, batch_slots=8, max_len=32, params=params,
                        mesh=mesh, page_size=4,
                        use_kernel=True, kernel_interpret=True)
    assert eng_s.sharded_kernel, arch
    assert "shard_map'd" in eng_s.explain_dispatch(), \\
        (arch, eng_s.explain_dispatch())
    out_s = drive(eng_s, cfg)
    counts = dict(attn_mod.DISPATCH_COUNTS)
    has_attn = any(k != "mamba" for k in cfg.pattern)
    if has_attn:
        # the fused kernel IS the dispatched path, never the mesh gather
        assert counts.get("kernel_sharded", 0) > 0, (arch, counts)
    assert counts.get("gather_mesh", 0) == 0, (arch, counts)
    eng_1 = ServeEngine(cfg, batch_slots=8, max_len=32, params=params,
                        page_size=4, use_kernel=True,
                        kernel_interpret=True)
    out_1 = drive(eng_1, cfg)
    eng_g = ServeEngine(cfg, batch_slots=8, max_len=32, params=params,
                        page_size=4, use_kernel=False)
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


def test_mesh_gather_fallback_is_loud(subproc):
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
# kernel explicitly off under a mesh -> gather path + one-line warning
eng = ServeEngine(cfg, batch_slots=8, max_len=32, params=params, mesh=mesh,
                  page_size=4, use_kernel=False)
assert not eng.sharded_kernel
assert "gather" in eng.explain_dispatch(), eng.explain_dispatch()
r = Request(0, prompt=list(np.arange(1, 9)), max_new=3)
eng.submit(r)
eng.run()
assert len(r.out) == 3
assert attn_mod.DISPATCH_COUNTS.get("gather_mesh", 0) > 0, \\
    dict(attn_mod.DISPATCH_COUNTS)
assert attn_mod.DISPATCH_COUNTS.get("kernel_sharded", 0) == 0
print("FALLBACK_OK")
""", devices=8)
    assert "FALLBACK_OK" in out
    assert "GSPMD dense gather path" in out  # the loud one-liner fired


def test_explain_dispatch_single_device():
    from repro.configs import get_config
    from repro.models.attention import explain_dispatch

    cfg = get_config("gemma2-27b-smoke")
    s = explain_dispatch(cfg, None, batch_slots=4, use_kernel=True)
    assert "single device" in s and "fused" in s
    s = explain_dispatch(cfg, None, batch_slots=4, use_kernel=False)
    assert "single device" in s and "gather" in s


def test_plan_infeasible_reasons():
    """paged_decode_plan explains WHY it falls back (surfaced in the
    warning and the startup banner)."""
    from repro.configs import get_config
    from repro.dist.sharding import paged_decode_plan

    cfg = get_config("gemma2-27b-smoke")
    plan, reason = paged_decode_plan(cfg, None, 8)
    assert plan is None and "single device" in reason

    class FakeMesh:
        shape = {"model": 4}
    plan, reason = paged_decode_plan(cfg, FakeMesh(), 8)
    assert plan is None and reason


def test_per_device_bytes_scale_with_live_pages_per_shard():
    """The acceptance account: per-device fused-decode HBM traffic is
    1/n_shards of the whole-pool traffic and scales linearly with live
    pages per shard; the gather path has no such term."""
    G, hd, P, M, B = 2, 64, 8, 16, 8
    for n_shards in (2, 4):
        sparse = sharded_decode_hbm_bytes(8, P, G, hd, n_shards=n_shards,
                                          batch=B, n_heads=4, max_pages=M)
        dense = sharded_decode_hbm_bytes(32, P, G, hd, n_shards=n_shards,
                                         batch=B, n_heads=4, max_pages=M)
        ratio = dense / sparse
        assert 2.0 < ratio <= 4.0, (n_shards, ratio)
        # sharding divides the per-device traffic
        single = decode_hbm_bytes(32, P, G, hd, batch=B, n_heads=4,
                                  max_pages=M)
        assert dense < single
        assert dense == pytest.approx(single / n_shards, rel=0.05)


def test_sharded_bytes_match_per_shard_account():
    """sharded bytes == the single-device model applied to one shard's
    share of pages and slots — the definition the kernel bench persists."""
    import math
    live, P, G, hd, B, M, nsh = 24, 8, 2, 64, 8, 16, 4
    got = sharded_decode_hbm_bytes(live, P, G, hd, n_shards=nsh, batch=B,
                                   n_heads=4, max_pages=M)
    want = decode_hbm_bytes(math.ceil(live / nsh), P, G, hd,
                            batch=math.ceil(B / nsh), n_heads=4, max_pages=M)
    assert got == want
