"""Distribution correctness on an 8-device host mesh (subprocess so the main
pytest process keeps 1 device): sharded step == single-device step, EP MoE ==
local MoE, compressed collectives, pod param sync, elastic reshard restore."""
import pytest


def test_sharded_train_step_matches_single_device(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.dist import sharding, annotate
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.train import optim, step as step_mod

cfg = get_config("phi4-mini-3.8b-smoke")
params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
opt = optim.init_opt(params)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                      cfg.vocab_size)}
step = step_mod.make_train_step(cfg, remat="none")
p_ref, _, m_ref = jax.jit(step)(params, opt, batch)

mesh = make_mesh((2, 4), ("data", "model"))
annotate.set_batch_axes(("data",))
psh = sharding.param_shardings(cfg, mesh, "tp")
params_s = jax.device_put(params, psh)
opt_s = optim.OptState(step=jax.device_put(opt.step),
                       m=jax.device_put(opt.m, psh),
                       v=jax.device_put(opt.v, psh))
with jax.set_mesh(mesh):
    p_sh, _, m_sh = jax.jit(step, in_shardings=(psh, None, None),
                            out_shardings=(psh, None, None))(
        params_s, opt_s, batch)
print("LOSS", float(m_ref["loss"]), float(m_sh["loss"]))
np.testing.assert_allclose(float(m_ref["loss"]), float(m_sh["loss"]),
                           rtol=1e-4)
for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=5e-3, atol=5e-4)
print("SHARDED_STEP_OK")
""", devices=8)
    assert "SHARDED_STEP_OK" in out


def test_moe_ep_matches_local(subproc):
    out = subproc("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import moe as moe_mod
from repro.models.common import init_params

cfg = get_config("olmoe-1b-7b-smoke")
# high capacity so EP reordering cannot change the capacity-drop set
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, capacity_factor=8.0))
params = init_params(moe_mod.moe_specs(cfg), jax.random.PRNGKey(0),
                     jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                      jnp.float32)
y_local, aux_local = moe_mod.moe(params, x, cfg)
mesh = make_mesh((2, 4), ("data", "model"))
with jax.set_mesh(mesh):
    y_ep, aux_ep = jax.jit(lambda p, x: moe_mod.moe(
        p, x, cfg, ep_axis="model", mesh=mesh))(params, x)
np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_ep),
                           rtol=2e-4, atol=2e-5)
# aux is a per-shard load-balance statistic (standard practice): only
# finiteness is required, not equality with the global statistic
assert np.isfinite(float(aux_ep))
print("MOE_EP_OK")
""", devices=8)
    assert "MOE_EP_OK" in out


def test_compressed_pmean_and_pod_sync(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.dist.collectives import compressed_pmean, pod_sync_params
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (2, 64)),
        "b": jax.random.normal(jax.random.PRNGKey(1), (32,))}
# per-pod different values: shard leading dim over pod inside shard_map
def body(t):
    return compressed_pmean(t, "pod")
with jax.set_mesh(mesh):
    f = jax.shard_map(body, mesh=mesh,
                      in_specs=({"w": P("pod", None), "b": P(None)},),
                      out_specs={"w": P("pod", None), "b": P(None)},
                      axis_names={"pod"}, check_vma=False)
    got = jax.jit(f)(tree)
want_w = jnp.mean(tree["w"], axis=0, keepdims=True)
# both pod-shards now hold the mean; int8 wire -> ~1% tolerance
np.testing.assert_allclose(np.asarray(got["w"][0]), np.asarray(want_w[0]),
                           rtol=0.05, atol=0.02)
np.testing.assert_allclose(np.asarray(got["w"][1]), np.asarray(want_w[0]),
                           rtol=0.05, atol=0.02)

# pod_sync_params: replicated params stay fixed under sync (mean of equals)
params = {"w": jax.random.normal(jax.random.PRNGKey(2), (8, 8))}
with jax.set_mesh(mesh):
    synced = jax.jit(lambda p: pod_sync_params(p, mesh))(params)
np.testing.assert_allclose(np.asarray(synced["w"]), np.asarray(params["w"]),
                           rtol=1e-6)
print("COLLECTIVES_OK")
""", devices=8)
    assert "COLLECTIVES_OK" in out


def test_elastic_reshard_restore(subproc):
    """Fault tolerance at scale: save on a (2,4) mesh, restore onto (4,2)
    and (1,8) meshes — elastic scaling across topologies."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from repro.ckpt import checkpoint as ck
from repro.configs import get_config
from repro.dist import sharding
from repro.launch.mesh import make_mesh
from repro.models import api

cfg = get_config("gemma2-27b-smoke")
params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
mesh1 = make_mesh((2, 4), ("data", "model"))
p1 = jax.device_put(params, sharding.param_shardings(cfg, mesh1, "fsdp_tp"))
d = tempfile.mkdtemp()
ck.save(d + "/step_1", p1, 1)
for shape in [(4, 2), (1, 8)]:
    mesh2 = make_mesh(shape, ("data", "model"))
    sh2 = sharding.param_shardings(cfg, mesh2, "tp")
    restored, step = ck.restore(d + "/step_1", jax.eval_shape(lambda: params),
                                shardings=sh2)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("ELASTIC_OK")
""", devices=8)
    assert "ELASTIC_OK" in out


def test_seq_sharded_decode_cache(subproc):
    """Decode with the KV cache sequence-sharded over the model axis equals
    unsharded decode (GSPMD partial-softmax reductions)."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.dist import sharding
from repro.launch.mesh import make_mesh
from repro.models import api, lm

cfg = get_config("phi4-mini-3.8b-smoke")
params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
B, S = 4, 32
toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
caches = lm.init_caches(cfg, B, S, dtype=jnp.float32)
step = lambda p, t, pos, c: lm.decode_step(p, t, pos, c, cfg)
ref_logits, ref_caches = None, caches
for i in range(4):
    ref_logits, ref_caches = jax.jit(step)(params, toks[:, i:i+1],
                                           jnp.full((B,), i, jnp.int32),
                                           ref_caches)
mesh = make_mesh((2, 4), ("data", "model"))
from repro.configs.base import SHAPES, ShapeConfig
shp = ShapeConfig("t", S, B, "decode")
cache_sh, _ = sharding.cache_shardings(cfg, shp, mesh)
psh = sharding.param_shardings(cfg, mesh, "tp")
with jax.set_mesh(mesh):
    params_s = jax.device_put(params, psh)
    caches_s = jax.device_put(lm.init_caches(cfg, B, S, dtype=jnp.float32),
                              cache_sh)
    jstep = jax.jit(step, in_shardings=(psh, None, None, cache_sh),
                    out_shardings=(None, cache_sh))
    logits = None
    for i in range(4):
        logits, caches_s = jstep(params_s, toks[:, i:i+1],
                                 jnp.full((B,), i, jnp.int32), caches_s)
np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                           rtol=2e-3, atol=2e-3)
print("SEQ_SHARDED_DECODE_OK")
""", devices=8)
    assert "SEQ_SHARDED_DECODE_OK" in out
