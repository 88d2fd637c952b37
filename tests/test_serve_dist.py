"""Multi-device serving on an 8-device host mesh (subprocess, like
test_dist): engine with params sharded via ``dist.param_shardings`` and
the page pool via ``dist.cache_shardings`` produces the same greedy outputs
as the single-device engine, including across a kv_quant variant
hot-swap."""
import pytest


def test_sharded_engine_matches_single_device(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.serve import serving_table
from repro.models import api
from repro.models.attention import PagedKVCache
from repro.serve.engine import Request, ServeEngine

cfg = get_config("phi4-mini-3.8b-smoke")
params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
table = serving_table(cfg, slots=4, max_len=32)
kvq_idx = len(table) - 1
assert table.variants[kvq_idx].knobs.kv_quant
rng = np.random.default_rng(1)
prompts = [list(rng.integers(1, cfg.vocab_size, 7)) for _ in range(9)]

def run(mesh):
    eng = ServeEngine(cfg, batch_slots=4, max_len=32, params=params,
                      table=table, mesh=mesh, prefill_chunk=3)
    reqs = [Request(i, prompt=p, max_new=5) for i, p in enumerate(prompts)]
    for r in reqs[:6]:
        eng.submit(r)
    eng.run()
    # hot-swap into the kv_quant variant (cache dtype conversion under the
    # mesh) and serve a second wave
    eng.set_variant(kvq_idx)
    for r in reqs[6:]:
        eng.submit(r)
    eng.run()
    return eng, [r.out for r in reqs]

eng_ref, ref = run(None)
mesh = make_mesh((2, 4), ("data", "model"))
eng_sh, got = run(mesh)
assert got == ref, (got, ref)
kv = [c for c in eng_sh.caches if isinstance(c, PagedKVCache)]
assert kv
for c in kv:
    assert c.kp.dtype == jnp.int8                      # converted under mesh
    # slot-affinity pool (dist.cache_shardings): pages over the batch axis;
    # the smoke kv_heads do not divide the model axis, so they replicate
    assert c.kp.sharding.spec == P(None, "data", None, None, None), \\
        c.kp.sharding
ps = jax.tree.leaves(eng_sh.params)
assert any("model" in (s.sharding.spec or ()) for s in ps), \\
    "params must be TP-sharded"
print("DIST_SERVE_OK")
""", devices=8)
    assert "DIST_SERVE_OK" in out
