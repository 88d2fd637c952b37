"""Kernel microbenchmarks: µs/call (CPU; Pallas interpret vs jnp reference)
and max abs error vs oracle. On TPU the same harness times the native path.

The paged-decode section also accounts *bytes moved*: the gather path's HBM
traffic comes from the compiled executable's ``cost_analysis`` (it scales
with slots x max_len — the dense gather buffer), the fused kernel's from its
per-live-page cost model — the numbers behind the explorer's paged decode
pricing, persisted to ``BENCH_kernels.json``."""
from __future__ import annotations

import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import RESULTS_DIR, Rows, timed
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.paged_attention import decode_hbm_bytes, paged_attention
from repro.kernels.ssd_scan import ssd_scan


def main(rows: Rows):
    # int8 matmul
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 512), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 256), jnp.float32)
    xq, xs = ref.quantize_rowwise(x)
    wq, ws = ref.quantize_rowwise(w, axis=0)
    t_ref, out_ref = timed(lambda: jax.block_until_ready(
        ref.int8_matmul_ref(xq, xs, wq, ws, jnp.float32)))
    t_k, out_k = timed(lambda: jax.block_until_ready(
        int8_matmul(xq, xs, wq, ws, out_dtype=jnp.float32, interpret=True,
                    bk=256)))
    err = float(jnp.max(jnp.abs(out_k - out_ref)))
    rows.add("kernel.int8_matmul.ref", t_ref * 1e6, "jnp oracle")
    rows.add("kernel.int8_matmul.pallas", t_k * 1e6,
             f"interpret;max_err={err:.2e}")

    # flash attention
    B, H, KVH, S, hd = 1, 4, 2, 512, 64
    q = jax.random.normal(jax.random.PRNGKey(2), (B, H, S, hd)) * 0.3
    k = jax.random.normal(jax.random.PRNGKey(3), (B, KVH, S, hd)) * 0.3
    v = jax.random.normal(jax.random.PRNGKey(4), (B, KVH, S, hd))
    t_ref, o_ref = timed(lambda: jax.block_until_ready(
        ref.mha_ref(q, k, v, causal=True)))
    t_k, o_k = timed(lambda: jax.block_until_ready(
        flash_attention(q, k, v, interpret=True)))
    err = float(jnp.max(jnp.abs(o_k - o_ref)))
    rows.add("kernel.flash_attention.ref", t_ref * 1e6, "jnp oracle")
    rows.add("kernel.flash_attention.pallas", t_k * 1e6,
             f"interpret;max_err={err:.2e}")
    t_p, _ = timed(lambda: jax.block_until_ready(
        flash_attention(q, k, v, interpret=True, kv_keep_stride=4)))
    rows.add("kernel.flash_attention.perforated", t_p * 1e6,
             "kv_keep_stride=4 (the attention-perforation knob)")

    # ssd scan
    B, S, Hh, P, N = 1, 256, 4, 64, 32
    x = jax.random.normal(jax.random.PRNGKey(5), (B, S, Hh, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(6), (B, S, Hh)))
    a = -jnp.exp(jax.random.uniform(jax.random.PRNGKey(7), (Hh,)))
    bb = jax.random.normal(jax.random.PRNGKey(8), (B, S, N)) * 0.5
    cc = jax.random.normal(jax.random.PRNGKey(9), (B, S, N)) * 0.5
    t_naive, o_naive = timed(lambda: jax.block_until_ready(
        ref.ssd_ref(x, dt, a, bb, cc)))
    t_chunk, o_chunk = timed(lambda: jax.block_until_ready(
        ref.ssd_chunked_ref(x, dt, a, bb, cc, chunk=64)))
    t_k, o_k = timed(lambda: jax.block_until_ready(
        ssd_scan(x, dt, a, bb, cc, chunk=64, interpret=True)))
    rows.add("kernel.ssd.naive_recurrence", t_naive * 1e6, "oracle")
    rows.add("kernel.ssd.chunked_jnp", t_chunk * 1e6,
             f"max_err={float(jnp.max(jnp.abs(o_chunk - o_naive))):.2e}")
    rows.add("kernel.ssd.pallas", t_k * 1e6,
             f"interpret;max_err={float(jnp.max(jnp.abs(o_k - o_naive))):.2e}")

    paged_decode_rows(rows)
    sharded_decode_rows(rows)
    prefill_rows(rows)
    return rows


def _paged_case(live_per_slot: int, *, B=4, G=2, R=2, hd=32, P=8, M=8,
                n_pages=40, quantized=False, seed=0):
    """Random paged pool with ``live_per_slot`` mapped pages per slot (the
    last one partial); returns the fused-kernel argument tuple, the pools
    stacked over one layer (read at layer 0)."""
    rng = np.random.default_rng(seed)
    if quantized:
        kp = rng.integers(-127, 128, (n_pages, P, G, hd)).astype(np.int8)
        vp = rng.integers(-127, 128, (n_pages, P, G, hd)).astype(np.int8)
    else:
        kp = (rng.normal(size=(n_pages, P, G, hd)) * 0.3).astype(np.float32)
        vp = rng.normal(size=(n_pages, P, G, hd)).astype(np.float32)
    block = np.zeros((B, M), np.int32)
    ppos = np.full((n_pages, P), -1, np.int32)
    pid = 1
    for b in range(B):
        for lp in range(live_per_slot):
            block[b, lp] = pid
            ppos[pid] = np.arange(lp * P, (lp + 1) * P)
            pid += 1
    position = np.full((B,), live_per_slot * P - P // 2 - 1, np.int32)
    q = (rng.normal(size=(B, G, R, hd)) * 0.3).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q, kp[None], vp[None], ppos[None],
                                          block, position))


def _gather_path(q, kp, vp, ppos, block, position, *, window=0, kv_scale=0.0):
    """The pre-kernel reference: materialize every block-table page into a
    dense (B, M*P) buffer, then one masked softmax (models.attention's
    ``_gather_pages`` path on raw arrays)."""
    from repro.models.attention import PagedKVCache, _gather_pages, _sdpa
    B, G, R, hd = q.shape
    cache = PagedKVCache(kp, vp, ppos, block)
    kk, vv, _, valid = _gather_pages(cache, 0, block, position[:, None],
                                     window=window)
    dq = (lambda a: a.astype(q.dtype) * kv_scale) if kv_scale else \
        (lambda a: a.astype(q.dtype))
    o = _sdpa(q[:, None], dq(kk), dq(vv), mask=valid[:, None, None])
    return o[:, 0]


def _compiled_bytes(fn, *args) -> float:
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):      # jax<=0.4.x drift
        cost = cost[0] if cost else {}
    return float(cost.get("bytes accessed", 0.0))


def paged_decode_rows(rows: Rows):
    """Fused paged-decode kernel vs the gather reference: µs/call + max err
    (fp32 / int8 KV / windowed) and the bytes-moved account showing fused
    HBM traffic scaling with LIVE pages while the gather path stays pinned
    at slots x max_len."""
    out = {}
    B, G, R, hd, P, M = 4, 2, 2, 32, 8, 8
    variants = [
        ("fp32", dict(), dict(quantized=False)),
        ("int8", dict(kv_scale=0.05), dict(quantized=True)),
        ("windowed", dict(window=16), dict(quantized=False)),
    ]
    for name, kw, mk in variants:
        q, kp, vp, ppos, block, position = _paged_case(4, B=B, G=G, R=R,
                                                       hd=hd, P=P, M=M, **mk)
        t_ref, o_ref = timed(lambda: jax.block_until_ready(
            _gather_path(q, kp, vp, ppos, block, position, **kw)))
        t_k, o_k = timed(lambda: jax.block_until_ready(
            paged_attention(q, kp, vp, ppos, block, position, 0,
                            interpret=True, **kw)))
        err = float(jnp.max(jnp.abs(o_k - o_ref)))
        rows.add(f"kernel.paged_decode.{name}.gather", t_ref * 1e6,
                 "jnp gather reference")
        rows.add(f"kernel.paged_decode.{name}.fused", t_k * 1e6,
                 f"interpret;max_err={err:.2e}")
        out[name] = {"gather_us": t_ref * 1e6, "fused_us": t_k * 1e6,
                     "max_err": err}

    # bytes moved per decode step: gather traffic is live-page-INVARIANT
    # (the dense buffer is always B x M x P), fused traffic is live pages
    kv_bytes = 4
    for label, live in (("sparse", 2), ("dense", 8)):
        q, kp, vp, ppos, block, position = _paged_case(live, B=B, G=G, R=R,
                                                       hd=hd, P=P, M=M)
        gather_b = _compiled_bytes(_gather_path, q, kp, vp, ppos, block,
                                   position)
        fused_b = decode_hbm_bytes(B * live, P, G, hd, kv_bytes=kv_bytes,
                                   batch=B, n_heads=G * R, max_pages=M)
        out[f"bytes_{label}"] = {
            "live_pages": B * live,
            "gather_bytes": gather_b,      # cost_analysis of the gather exe
            "fused_bytes": fused_b,        # kernel cost model: O(live pages)
        }
        rows.add(f"kernel.paged_decode.bytes.{label}", fused_b,
                 f"live_pages={B * live};gather_bytes={gather_b:.0f}")
    (RESULTS_DIR / "BENCH_kernels.json").write_text(json.dumps(out, indent=1))
    return rows


# ----------------------------------------------------- sharded decode rows --
# The multi-device fast path: the fused kernel shard_map'd over the
# slot-affinity pool layout (models.attention._sharded_write_attend) vs the
# GSPMD dense-gather fallback, on 8 simulated devices. Runs in a subprocess
# because the device count is fixed at jax import.

_SHARD_B, _SHARD_G, _SHARD_R, _SHARD_HD = 8, 2, 2, 32
_SHARD_P, _SHARD_M, _SHARD_NSH = 8, 8, 4
_SHARD_PAGES = 80                       # 4 shards x 20 (null + 16 live + slack)


def _sharded_paged_case(live_per_slot: int, *, quantized=False, seed=0):
    """Slot-affinity layout: slot b's pages all come from the contiguous
    page range of shard ``b * n_shards // B``; each shard's first page is
    its local null sentinel (never mapped). The pools are stacked over one
    layer (read at layer 0). Also returns the step's new K/V entries so the
    write+attend region can be benched as one unit."""
    B, G, hd = _SHARD_B, _SHARD_G, _SHARD_HD
    Pg, nsh, n_pages = _SHARD_P, _SHARD_NSH, _SHARD_PAGES
    chunk = n_pages // nsh
    rng = np.random.default_rng(seed)
    if quantized:
        kp = rng.integers(-127, 128, (n_pages, Pg, G, hd)).astype(np.int8)
        vp = rng.integers(-127, 128, (n_pages, Pg, G, hd)).astype(np.int8)
        knew = rng.integers(-127, 128, (B, G, hd)).astype(np.int8)
        vnew = rng.integers(-127, 128, (B, G, hd)).astype(np.int8)
    else:
        kp = (rng.normal(size=(n_pages, Pg, G, hd)) * 0.3).astype(np.float32)
        vp = rng.normal(size=(n_pages, Pg, G, hd)).astype(np.float32)
        knew = (rng.normal(size=(B, G, hd)) * 0.3).astype(np.float32)
        vnew = rng.normal(size=(B, G, hd)).astype(np.float32)
    block = np.zeros((B, _SHARD_M), np.int32)
    ppos = np.full((n_pages, Pg), -1, np.int32)
    nxt = [s * chunk + 1 for s in range(nsh)]
    for b in range(B):
        s = b * nsh // B
        for lp in range(live_per_slot):
            pid = nxt[s]
            nxt[s] += 1
            block[b, lp] = pid
            ppos[pid] = np.arange(lp * Pg, (lp + 1) * Pg)
    position = np.full((B,), live_per_slot * Pg - Pg // 2 - 1, np.int32)
    q = (rng.normal(size=(B, G, _SHARD_R, hd)) * 0.3).astype(np.float32)
    return q, kp[None], vp[None], ppos[None], block, position, knew, vnew


def _sharded_child():
    """Runs under XLA_FLAGS=--xla_force_host_platform_device_count=8; prints
    one SHARDED_JSON line the parent merges into BENCH_kernels.json."""
    from jax.sharding import NamedSharding, PartitionSpec as Psp

    from repro.dist.sharding import PagedDecodePlan
    from repro.kernels.paged_attention import sharded_decode_hbm_bytes
    from repro.launch.mesh import make_mesh
    from repro.models import attention as attn_mod

    assert jax.device_count() >= 8, jax.device_count()
    B, G, R, hd = _SHARD_B, _SHARD_G, _SHARD_R, _SHARD_HD
    Pg, M, nsh = _SHARD_P, _SHARD_M, _SHARD_NSH
    mesh = make_mesh((nsh, 2), ("data", "model"))
    plan = PagedDecodePlan("data", nsh, "model")
    active = jnp.ones((B,), bool)
    out = {"mesh": {"data": nsh, "model": 2}, "n_shards": nsh}

    def fused_fn(window, kv_scale):
        return jax.jit(functools.partial(
            attn_mod._sharded_write_attend, mesh=mesh, plan=plan,
            window=window, kv_scale=kv_scale, cap=0.0, interpret=True))

    def gather_jit(window, kv_scale):
        sh = lambda *s: NamedSharding(mesh, Psp(*s))
        pool = sh(None, "data", None, "model", None)
        return jax.jit(
            functools.partial(_gather_path, window=window,
                              kv_scale=kv_scale),
            in_shardings=(sh("data", "model"), pool, pool,
                          sh(None, "data", None), sh("data", None),
                          sh("data")))

    def written_pool(case):
        # the gather comparator attends a pre-written pool: emulate the
        # step's dynamic write on host so both paths see identical caches
        q, kp, vp, ppos, block, position, knew, vnew = case
        kp2, vp2 = kp.copy(), vp.copy()
        for b in range(B):
            phys, off = block[b, position[b] // Pg], position[b] % Pg
            kp2[0, phys, off], vp2[0, phys, off] = knew[b], vnew[b]
        return kp2, vp2

    variants = [("fp32", dict(window=0, kv_scale=0.0), dict()),
                ("int8", dict(window=0, kv_scale=0.05),
                 dict(quantized=True)),
                ("windowed", dict(window=16, kv_scale=0.0), dict())]
    for name, kw, mk in variants:
        case = _sharded_paged_case(4, **mk)
        q, kp, vp, ppos, block, position, knew, vnew = case
        cache = attn_mod.PagedKVCache(*map(jnp.asarray,
                                           (kp, vp, ppos, block[None])))
        ff = fused_fn(**kw)
        args = tuple(map(jnp.asarray, (q, knew, vnew, position)))
        layer = jnp.asarray(0, jnp.int32)
        t_f, (o_f, _) = timed(lambda: jax.block_until_ready(
            ff(*args, active, cache, layer)))
        kp2, vp2 = written_pool(case)
        gf = gather_jit(**kw)
        gargs = tuple(map(jnp.asarray, (q, kp2, vp2, ppos, block, position)))
        t_g, o_g = timed(lambda: jax.block_until_ready(gf(*gargs)))
        err = float(jnp.max(jnp.abs(o_f - o_g)))
        out[name] = {"gather_gspmd_us": t_g * 1e6,
                     "fused_sharded_us": t_f * 1e6, "max_err": err}

    # per-device bytes: fused traffic scales with live pages PER SHARD;
    # gather from the compiled GSPMD executable's cost_analysis
    for label, live in (("sparse", 2), ("dense", 8)):
        case = _sharded_paged_case(live)
        q, kp, vp, ppos, block, position, knew, vnew = case
        kp2, vp2 = written_pool(case)
        gf = gather_jit(0, 0.0)
        gargs = tuple(map(jnp.asarray, (q, kp2, vp2, ppos, block, position)))
        cost = gf.lower(*gargs).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        per_dev = sharded_decode_hbm_bytes(
            B * live, Pg, G, hd, n_shards=nsh, kv_bytes=4, batch=B,
            n_heads=G * R, max_pages=M)
        total = sharded_decode_hbm_bytes(
            B * live, Pg, G, hd, n_shards=1, kv_bytes=4, batch=B,
            n_heads=G * R, max_pages=M)
        out[f"bytes_{label}"] = {
            "live_pages": B * live,
            "live_per_shard": B * live // nsh,
            "gather_bytes": float(cost.get("bytes accessed", 0.0)),
            "fused_bytes_per_device": per_dev,
            "fused_bytes_total": total,
        }
    print("SHARDED_JSON:" + json.dumps(out))


def sharded_decode_rows(rows: Rows):
    """Spawn the 8-device child, merge its account under ``sharded`` in
    BENCH_kernels.json, and emit the comparison rows."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.kernel_bench", "--sharded-child"],
        capture_output=True, text=True, env=env)
    line = next((l for l in proc.stdout.splitlines()
                 if l.startswith("SHARDED_JSON:")), None)
    assert line is not None, (proc.stdout, proc.stderr[-2000:])
    sharded = json.loads(line[len("SHARDED_JSON:"):])
    path = RESULTS_DIR / "BENCH_kernels.json"
    out = json.loads(path.read_text())
    out["sharded"] = sharded
    path.write_text(json.dumps(out, indent=1))
    for name in ("fp32", "int8", "windowed"):
        s = sharded[name]
        rows.add(f"kernel.paged_decode.sharded.{name}.gather_gspmd",
                 s["gather_gspmd_us"], "GSPMD dense-gather fallback")
        rows.add(f"kernel.paged_decode.sharded.{name}.fused",
                 s["fused_sharded_us"],
                 f"shard_map x{sharded['n_shards']};interpret;"
                 f"max_err={s['max_err']:.2e}")
    for label in ("sparse", "dense"):
        b = sharded[f"bytes_{label}"]
        rows.add(f"kernel.paged_decode.sharded.bytes.{label}",
                 b["fused_bytes_per_device"],
                 f"live_per_shard={b['live_per_shard']};"
                 f"total={b['fused_bytes_total']:.0f};"
                 f"gather_bytes={b['gather_bytes']:.0f}")
    return rows


# ------------------------------------------------------- ring prefill rows --
# The sequence-parallel admission path: kernels.ring_attention shard_map'd
# over the prefill plan's ring vs the unsharded masked-softmax oracle, on 8
# simulated devices (subprocess — device count is fixed at jax import). The
# parent also stamps the 32k-target per-device cost model the explorer
# prices admission with; CI asserts per-device work scales ~1/n_shards.

_PRE_B, _PRE_C, _PRE_CACHE = 1, 64, 192
_PRE_G, _PRE_R, _PRE_HD = 2, 2, 32
_PRE_NSH = 4


def _prefill_case(quantized=False, seed=0):
    """One admission chunk (positions cache..cache+C) over its full visible
    context [cache; chunk] with a hole punched in the cache positions —
    exercises the -1-position masking the paged gather path produces."""
    B, C, G, R, hd = _PRE_B, _PRE_C, _PRE_G, _PRE_R, _PRE_HD
    L = _PRE_CACHE + C
    rng = np.random.default_rng(seed)
    if quantized:
        k = rng.integers(-127, 128, (B, L, G, hd)).astype(np.int8)
        v = rng.integers(-127, 128, (B, L, G, hd)).astype(np.int8)
    else:
        k = (rng.normal(size=(B, L, G, hd)) * 0.3).astype(np.float32)
        v = rng.normal(size=(B, L, G, hd)).astype(np.float32)
    q = (rng.normal(size=(B, C, G, R, hd)) * 0.3).astype(np.float32)
    q_pos = np.broadcast_to(np.arange(_PRE_CACHE, L, dtype=np.int32),
                            (B, C)).copy()
    kv_pos = np.broadcast_to(np.arange(L, dtype=np.int32), (B, L)).copy()
    kv_pos[:, 7:11] = -1                     # unmapped hole in the cache
    return tuple(jnp.asarray(a) for a in (q, k, v, q_pos, kv_pos))


def _prefill_ref(q, k, v, qp, kvp, *, window=0, cap=0.0, kv_scale=0.0):
    """Unsharded oracle: one masked softmax over the whole context with the
    same explicit-position mask the ring kernel applies."""
    dq = (lambda a: a.astype(jnp.float32) * kv_scale) if kv_scale else \
        (lambda a: a.astype(jnp.float32))
    k, v = dq(k), dq(v)
    hd = q.shape[-1]
    s = jnp.einsum("bcgrd,blgd->bgrcl", q.astype(jnp.float32),
                   k) * hd ** -0.5
    if cap:
        s = cap * jnp.tanh(s / cap)
    qe = qp[:, None, None, :, None]
    ke = kvp[:, None, None, None, :]
    mask = (ke >= 0) & (qe >= 0) & (ke <= qe)
    if window:
        mask &= ke > qe - window
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bgrcl,blgd->bcgrd", p, v).astype(q.dtype)


def _prefill_child():
    """Runs under XLA_FLAGS=--xla_force_host_platform_device_count=8; prints
    one PREFILL_JSON line the parent merges into BENCH_kernels.json."""
    from repro.dist.sharding import PrefillPlan
    from repro.kernels.ring_attention import ring_chunk_attention
    from repro.launch.mesh import make_mesh

    assert jax.device_count() >= 8, jax.device_count()
    nsh = _PRE_NSH
    mesh = make_mesh((nsh, 2), ("data", "model"))
    plan = PrefillPlan("data", nsh, "model")
    out = {"mesh": {"data": nsh, "model": 2}, "n_shards": nsh,
           "chunk_len": _PRE_C, "kv_len": _PRE_CACHE + _PRE_C}
    variants = [("fp32", dict(), dict()),
                ("int8", dict(kv_scale=0.05), dict(quantized=True)),
                ("windowed", dict(window=32), dict())]
    for name, kw, mk in variants:
        q, k, v, qp, kvp = _prefill_case(**mk)
        rf = jax.jit(functools.partial(_prefill_ref, **kw))
        t_u, o_u = timed(lambda: jax.block_until_ready(rf(q, k, v, qp, kvp)))
        ring = jax.jit(functools.partial(ring_chunk_attention, mesh=mesh,
                                         plan=plan, interpret=True, **kw))
        t_r, o_r = timed(lambda: jax.block_until_ready(
            ring(q, k, v, qp, kvp)))
        err = float(jnp.max(jnp.abs(o_r - o_u)))
        out[name] = {"unsharded_us": t_u * 1e6, "ring_us": t_r * 1e6,
                     "max_err": err}
    print("PREFILL_JSON:" + json.dumps(out))


def prefill_rows(rows: Rows):
    """Spawn the 8-device ring-prefill child, merge its parity account plus
    the 32k-per-device cost model under ``prefill`` in BENCH_kernels.json."""
    from repro.kernels.ring_attention import (
        prefill_attn_flops, prefill_hbm_bytes, sharded_prefill_attn_flops,
        sharded_prefill_hbm_bytes)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.kernel_bench", "--prefill-child"],
        capture_output=True, text=True, env=env)
    line = next((l for l in proc.stdout.splitlines()
                 if l.startswith("PREFILL_JSON:")), None)
    assert line is not None, (proc.stdout, proc.stderr[-2000:])
    prefill = json.loads(line[len("PREFILL_JSON:"):])
    # the 32k target shape the ISSUE's admission cell is sized for: 2k
    # chunks over a 32k context, 16 heads of 128 at 8-way GQA
    C32, L32, H32, G32, HD32 = 2048, 32768, 16, 8, 128
    nsh = prefill["n_shards"]
    prefill["flops_32k"] = {
        "total": prefill_attn_flops(C32, L32, H32, HD32),
        "per_device": sharded_prefill_attn_flops(C32, L32, H32, HD32,
                                                 n_shards=nsh),
    }
    for tag, kv_b in (("", 4), ("_int8", 1)):
        prefill[f"bytes_32k{tag}"] = {
            "total": prefill_hbm_bytes(C32, L32, G32, HD32, n_heads=H32,
                                       kv_bytes=kv_b),
            "per_device": sharded_prefill_hbm_bytes(
                C32, L32, G32, HD32, n_shards=nsh, n_heads=H32,
                kv_bytes=kv_b),
        }
    path = RESULTS_DIR / "BENCH_kernels.json"
    out = json.loads(path.read_text())
    out["prefill"] = prefill
    path.write_text(json.dumps(out, indent=1))
    for name in ("fp32", "int8", "windowed"):
        s = prefill[name]
        rows.add(f"kernel.ring_prefill.{name}.unsharded", s["unsharded_us"],
                 "jnp masked-softmax oracle")
        rows.add(f"kernel.ring_prefill.{name}.ring", s["ring_us"],
                 f"shard_map x{nsh};interpret;max_err={s['max_err']:.2e}")
    for key in ("flops_32k", "bytes_32k", "bytes_32k_int8"):
        w = prefill[key]
        rows.add(f"kernel.ring_prefill.{key}.per_device", w["per_device"],
                 f"total={w['total']:.3g};"
                 f"scaling=x{w['total'] / w['per_device']:.2f}/{nsh}")
    return rows


if __name__ == "__main__":
    if "--sharded-child" in sys.argv:
        _sharded_child()
    elif "--prefill-child" in sys.argv:
        _prefill_child()
