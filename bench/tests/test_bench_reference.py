"""Each plain reference agrees with the program's model at a small size in
float32, on the benchmark's own weights."""
import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import system, weights
from harness.main import BENCH, load_module

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.mark.parametrize("name", ["tiny_dense", "tiny_ssd"])
def test_reference_matches_program_forward(name):
    from repro.models import api, lm
    cfg = json.loads((DATA / f"{name}.json").read_text())
    ref = load_module(BENCH / "configs" / f"{cfg['reference']}.py")
    layout = ref.param_layout(cfg)
    pcfg = system.program_config(cfg)
    weights.check_against(layout, api.abstract(pcfg, jnp.bfloat16))
    params = weights.make(layout, 2**31 + 5)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    toks = np.random.default_rng(0).integers(1, cfg["vocab_size"], 48)
    with jax.default_matmul_precision("highest"):
        h, _ = lm.forward_hidden(p32, jnp.asarray(toks[None]), pcfg,
                                 remat="none")
        want = np.asarray(lm.logits_fn(p32, h, pcfg))[0]
    got = ref.logits_at(p32, toks, 0, cfg)
    assert got.shape == want.shape
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-4, err


def test_layout_mismatch_is_refused():
    from repro.models import api
    cfg = json.loads((DATA / "tiny_dense.json").read_text())
    ref = load_module(BENCH / "configs" / "dense_gqa_ref.py")
    other = dataclasses.replace(system.program_config(cfg), d_ff=256)
    with pytest.raises(RuntimeError):
        weights.check_against(ref.param_layout(cfg), api.abstract(other))


def test_weights_repeat_per_seed_and_big_seeds_differ():
    cfg = json.loads((DATA / "tiny_dense.json").read_text())
    ref = load_module(BENCH / "configs" / "dense_gqa_ref.py")
    lay = ref.param_layout(cfg)
    a = weights.make(lay, 2**31 + 7)["embed"]
    b = weights.make(lay, 2**31 + 7)["embed"]
    c = weights.make(lay, 2**31 + 7 + 2**32)["embed"]
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
