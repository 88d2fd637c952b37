"""The MoE/MLA plain reference against the program at a small size, the
harness's layout check on it, and the readers of the Moonlight cell's
per-layer metrics."""
import dataclasses
import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import moe_mla, system, weights
from harness.main import BENCH, load_module

DATA = pathlib.Path(__file__).resolve().parent / "data"


def tiny():
    cfg = json.loads((DATA / "tiny_moe_mla.json").read_text())
    return cfg, load_module(BENCH / "configs" / f"{cfg['reference']}.py")


def test_reference_matches_program_forward():
    from repro.models import api, lm
    cfg, ref = tiny()
    layout = ref.param_layout(cfg)
    pcfg = system.program_config(cfg)
    weights.check_against(layout, api.abstract(pcfg, jnp.bfloat16))
    params = weights.make(layout, 2**31 + 5)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    moe = p32["groups"]["pos0"]["moe"]        # a random correction bias
    moe["score_bias"] = jax.random.normal(jax.random.PRNGKey(1),
                                          moe["score_bias"].shape) * 0.3
    toks = np.random.default_rng(0).integers(1, cfg["vocab_size"], 48)
    with jax.default_matmul_precision("highest"):
        h, _ = lm.forward_hidden(p32, jnp.asarray(toks[None]), pcfg,
                                 remat="none")
        want = np.asarray(lm.logits_fn(p32, h, pcfg))[0]
    got = ref.logits_at(p32, toks, 0, cfg)
    assert got.shape == want.shape
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-4, err


@pytest.mark.parametrize("field,value", [("held", (0, 2)),
                                         ("d_shared", 64)])
def test_layout_mismatch_is_refused(field, value):
    from repro.models import api
    cfg, ref = tiny()
    pcfg = system.program_config(cfg)
    other = dataclasses.replace(pcfg, moe=dataclasses.replace(
        pcfg.moe, **{field: value}))
    with pytest.raises(RuntimeError):
        weights.check_against(ref.param_layout(cfg), api.abstract(other))


def test_work_from_shapes_at_published_widths():
    cfg = json.loads((BENCH / "configs" / "moonlight-16b-a3b.json")
                     .read_text())
    # one token at position 99 reads 100 entries of 576 bf16 in 27 layers
    assert moe_mla.latent_decode_token_bytes(cfg, 99) == 27 * 100 * 1152
    assert moe_mla.latent_decode_token_flops(cfg, 99) == \
        27 * 2 * 16 * 100 * (576 + 512)
    assert moe_mla.expert_bytes(cfg, rows=10, active=2) == \
        2 * 3 * 2048 * 1408 * 2 + 10 * 2048 * 4
    assert moe_mla.expert_flops(cfg, 10) == 10 * 6 * 2048 * 1408


def test_counters_and_readers_follow_the_engine():
    """The expert counters of a served window, read by the two counter
    metrics from the engine's stamped marks (none without an engine that
    keeps them)."""
    from repro.configs import get_config
    from repro.models import api
    from repro.serve.engine import Request, ServeEngine
    cfg, _ = tiny()
    assert moe_mla.counters_between(0.0, 1.0) in (None, (0, 0))
    pcfg = get_config(cfg["registry"])
    eng = ServeEngine(pcfg, batch_slots=2, max_len=64,
                      params=api.init(pcfg, jax.random.PRNGKey(0),
                                      jnp.float32),
                      prefill_chunk=8, paged=True, page_size=4,
                      megastep_k=4)
    for i in range(3):
        eng.submit(Request(i, list(range(1, 12 + 5 * i)), max_new=6))
    eng.run()
    marks = list(eng.counter_marks)
    assert marks and marks[-1][1:] == (eng.stats["moe_rows"],
                                       eng.stats["moe_active_experts"])
    t0, t1 = marks[0][0] - 1, marks[-1][0] + 1
    assert moe_mla.counters_between(t0, t1) == marks[-1][1:]
    out = types.SimpleNamespace(window=(t0, t1), records=[])
    rows = load_module(BENCH / "metrics" / "moe.rows_per_active_expert.py")
    assert rows.read(dict(out=out)) == pytest.approx(
        marks[-1][1] / marks[-1][2])
    roof = load_module(BENCH / "metrics" / "kernel.moe_experts_roofline.py")
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    trace = dict(ops=[["%moe_experts.3 = bf16[64,64] custom-call()",
                       0, 2_000_000]], lo=0, hi=10_000_000,
                 host_window=(t0, t1))
    r, a = marks[-1][1:]
    need = max(moe_mla.expert_bytes(cfg, r, a) / peaks["hbm_bytes_per_s"],
               moe_mla.expert_flops(cfg, r) / peaks["bf16_flops"])
    got = roof.read(dict(trace=trace, peaks=peaks, cfg=cfg, out=out))
    assert got == pytest.approx(100 * need / 2e-3)
    del eng


def test_latent_roofline_reads_the_kernel_by_name():
    cfg = json.loads((BENCH / "configs" / "moonlight-16b-a3b.json")
                     .read_text())
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    roof = load_module(BENCH / "metrics" /
                       "kernel.latent_decode_roofline.py")
    rec = types.SimpleNamespace(prompt_len=100, stamps=[0.5, 1.0, 1.5, 9.0])
    out = types.SimpleNamespace(records=[rec])
    ops = [["%paged_latent_decode.7 = bf16[48,16,512] custom-call()", 0,
            1_000_000],
           ["%moe_experts.2 = bf16[64,2048] custom-call()", 0, 5_000_000]]
    trace = dict(ops=ops, lo=0, hi=10_000_000, host_window=(0.0, 2.0))
    got = roof.read(dict(trace=trace, peaks=peaks, cfg=cfg, out=out))
    # decode tokens at positions 100 and 101 (the first stamp is the
    # admission's token), against 1 ms of the kernel's own events
    need = sum(moe_mla.latent_decode_token_bytes(cfg, p)
               for p in (100, 101)) / peaks["hbm_bytes_per_s"]
    assert got == pytest.approx(100 * need / 1e-3)
    trace["ops"] = ops[1:]                   # the kernel did not run
    assert roof.read(dict(trace=trace, peaks=peaks, cfg=cfg,
                          out=out)) is None
