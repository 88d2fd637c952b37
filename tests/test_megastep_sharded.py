"""Megastep decode on an 8-simulated-device mesh (subprocess, like
test_sharded_decode): the fused K-token dispatch must be token-identical to
the single-device engine at K=1 across all four cache families, and the
donation + async-pipeline machinery must survive an elastic revoke/restore
mid-run with zero dropped requests — the drain point (`_drain_pipeline`)
flushes the in-flight megastep before cache surgery, and the re-homed
executables re-donate."""

ARCHS = ["phi4-mini-3.8b-smoke",   # MHA
         "gemma2-27b-smoke",       # GQA + local attention
         "zamba2-2.7b-smoke",      # hybrid attn/SSM
         "mamba2-780m-smoke"]      # pure SSM


def test_sharded_megastep_token_parity(subproc):
    out = subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import api
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
    eng_m = ServeEngine(cfg, batch_slots=8, max_len=32, params=params,
                        mesh=mesh, page_size=4,
                        use_kernel=True, kernel_interpret=True,
                        megastep_k=4)
    assert "megastep scan" in eng_m.explain_dispatch()
    out_m = drive(eng_m, cfg)
    assert eng_m.row_dispatches / max(eng_m.row_tokens, 1) <= 1.0
    eng_1 = ServeEngine(cfg, batch_slots=8, max_len=32, params=params,
                        page_size=4, use_kernel=True,
                        kernel_interpret=True)
    out_1 = drive(eng_1, cfg)
    assert out_m == out_1, (arch, out_m, out_1)
    assert all(len(t) == 5 for t in out_m), out_m
    eng_m.pool.assert_consistent()
    print("MEGA_PARITY_OK", arch)
print("ALL_OK")
""" % ARCHS, devices=8)
    assert "ALL_OK" in out
    for arch in ARCHS:
        assert f"MEGA_PARITY_OK {arch}" in out


def test_megastep_donation_survives_revoke_restore(subproc):
    """Chaos interleaving: revoke 2 of 8 devices mid-run (grace deadline)
    and restore them later while the engine runs DONATED megasteps through
    the async double-buffered pipeline. The re-home must drain the
    in-flight megastep, migrate pages, rebuild (and re-donate) the
    executables, and complete every request token-identical to the
    unfaulted megastep run — zero drops."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.dist import elastic
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.serve.engine import Request, ServeEngine

cfg = get_config("phi4-mini-3.8b-smoke")
params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
rng = np.random.default_rng(11)
prompts = [list(rng.integers(1, cfg.vocab_size, 7)) for _ in range(8)]

def run(script):
    mesh = make_mesh((4, 2), ("data", "model"))
    eng = ServeEngine(cfg, batch_slots=4, max_len=32, params=params,
                      mesh=mesh, page_size=4, prefill_chunk=3,
                      megastep_k=4)
    assert eng.donate
    reqs = [Request(i, prompt=list(p), max_new=6)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    inj = elastic.FaultInjector.parse(script) if script else None
    steps = 0
    while not eng.idle and steps < 2000:
        if inj is not None:
            for ev in inj.due(steps):
                eng.inject(ev)
        eng.step()
        steps += 1
    assert eng.idle, "drained"
    return eng, reqs

ref_eng, ref = run("")
eng, got = run("revoke@4+2:2,restore@9")
assert all(r.done for r in got), [r.uid for r in got if not r.done]
assert not eng.rejected, "zero dropped requests"
assert [r.out for r in got] == [r.out for r in ref], "token parity"
assert eng.stats["rehomes"] == 2
# the in-flight megastep was flushed, not leaked, across both re-homes
assert eng._inflight is None and eng._carry is None
print("MEGA_CHAOS_OK")
""", devices=8)
    assert "MEGA_CHAOS_OK" in out
