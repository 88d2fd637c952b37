"""The system under test: the program's serving engine, built the way
``repro.launch.serve.serve`` builds it (paged pool, megastep decode, cache
dtype equal to the params dtype, greedy, no runtime and no variant table so
the precise rung stays pinned), and its warm-up.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pathlib
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

WARM_UID = 1 << 30            # warm-up request ids, clear of the traffic's


def program_config(bench_cfg: dict):
    """The program's ModelConfig for a bench configuration file: the registry
    entry with every size the file states applied over it."""
    from repro.configs import get_config
    from repro.configs.base import SSMConfig
    base = get_config(bench_cfg["registry"])
    if bench_cfg["family"] == "dense":
        kw = dict(n_layers=bench_cfg["num_hidden_layers"],
                  d_model=bench_cfg["hidden_size"],
                  n_heads=bench_cfg["num_attention_heads"],
                  n_kv_heads=bench_cfg["num_key_value_heads"],
                  head_dim=bench_cfg["head_dim"],
                  d_ff=bench_cfg["intermediate_size"],
                  vocab_size=bench_cfg["vocab_size"],
                  rope_theta=bench_cfg["rope_theta"],
                  norm_eps=bench_cfg["rms_norm_eps"],
                  tie_embeddings=bench_cfg["tie_word_embeddings"])
    else:
        s = bench_cfg["ssm_cfg"]
        kw = dict(n_layers=bench_cfg["n_layer"], d_model=bench_cfg["d_model"],
                  vocab_size=bench_cfg["vocab_size"],
                  norm_eps=bench_cfg["norm_epsilon"],
                  tie_embeddings=bench_cfg["tie_embeddings"],
                  ssm=SSMConfig(d_state=s["d_state"], expand=s["expand"],
                                head_dim=s["headdim"], conv_width=s["d_conv"],
                                chunk=s["chunk_size"]))
    return dataclasses.replace(base, **kw)


def knobs_for(control: str):
    """The program's own rung for a control run ('' = the precise rung)."""
    from repro.approx.knobs import PRECISE, ApproxKnobs
    if not control:
        return PRECISE
    if control == "int8":
        return ApproxKnobs(matmul_precision="int8", kv_quant=True)
    raise ValueError(f"unknown control {control!r}")


def build_engine(cfg, params, eng_spec: dict, seed: int, knobs):
    from repro.serve.engine import ServeEngine
    return ServeEngine(
        cfg, batch_slots=eng_spec["slots"], max_len=eng_spec["max_len"],
        params=params, knobs=knobs, table=None, runtime=None,
        temperature=0.0, mesh=None, prefill_chunk=eng_spec["prefill_chunk"],
        seed=seed & 0x7FFFFFFF, cache_dtype=jnp.bfloat16, paged=True,
        page_size=eng_spec["page_size"], n_pages=eng_spec["pool_pages"],
        megastep_k=eng_spec["megastep"], eos_id=-1)


def _admission_args(eng, C: int):
    return (eng.params, jnp.zeros((1, C), jnp.int32),
            jnp.asarray(0, jnp.int32), eng.caches, jnp.asarray(0, jnp.int32))


def prime_marker(cache_dir: str, cfg: dict) -> pathlib.Path:
    """The marker of a primed cache: one per configuration as run (sizes,
    engine shape) and JAX version, since each changes the programs."""
    key = json.dumps(cfg, sort_keys=True) + jax.__version__
    h = hashlib.sha256(key.encode()).hexdigest()[:16]
    return pathlib.Path(cache_dir) / "bench-primed" / f"{cfg['name']}-{h}"


def prime_admission_tails(eng, marker: pathlib.Path, log) -> int:
    """Compile every admission chunk length 1..chunk into the persistent
    cache. The engine keeps only a bounded LRU of admission executables, so
    a ragged tail it evicted is re-traced in the window; primed, that costs
    a cache load and not a compile. ``marker`` records a primed cache
    directory, but the marker is trusted only while a probe of a few tails,
    the first primed and the rarest in traffic, compiles nothing: every
    program it obtains is a load from the cache (``log`` records both). A
    cache that lost entries is primed again. Returns the number of tails primed (0 when the probe
    passed)."""
    C = eng.prefill_chunk
    if marker.exists():
        n0 = len(log.events)
        for c in sorted({1, 2, C // 2, C - 1}):
            _compile_tail(eng, c)
        kinds = [e[0] for e in log.events[n0:]]
        if kinds.count("compile") == kinds.count("cache_load"):
            return 0                  # every program the probe got was a load
        marker.unlink()
    for c in range(1, C + 1):
        _compile_tail(eng, c)
    marker.parent.mkdir(parents=True, exist_ok=True)
    marker.write_text(f"admission tails 1..{C} primed\n")
    return C


def _compile_tail(eng, c: int) -> None:
    eng._prefill_exe(c).lower(*_admission_args(eng, c)).compile()


def warm_megasteps(eng) -> None:
    """Run the megastep executable for every K the engine can pick
    (1..megastep_k) with no live row: every row is masked, so the pool and
    state come back unchanged (the engine's freeze contract)."""
    B = eng.batch_slots
    z = jnp.zeros(B, jnp.int32)
    dead = jnp.zeros(B, bool)
    for k in range(1, eng.megastep_k + 1):
        out = eng._megastep_exe(k)(eng.params, z, z, dead, z, z, z,
                                   eng.caches)
        eng.caches = out[-1]
    jax.block_until_ready(eng.caches)


def warm_traffic(eng, vocab: int, seed: int) -> None:
    """Serve one request per slot through the public path (submit/step):
    a full chunk plus a ragged tail each, so every slot's host-side
    programs (state seeding, snapshots, block pushes) and the full-chunk
    admission run once before the window."""
    from repro.serve.engine import Request
    rng = np.random.default_rng([seed, 0xA11])
    C = eng.prefill_chunk
    reqs: List[Request] = []
    for i in range(eng.batch_slots):
        n = C + 1 + (i * 37) % (C - 1)
        reqs.append(Request(WARM_UID + i, list(rng.integers(1, vocab, n)),
                            max_new=2 + i % 8))
        eng.submit(reqs[-1])
    t0 = time.perf_counter()
    while not eng.idle:
        eng.step()
        if time.perf_counter() - t0 > 600:
            raise RuntimeError("warm-up did not finish in 600 s")
    bad = [r.uid for r in reqs if not r.done]
    if bad:
        raise RuntimeError(f"warm-up requests not served: {bad}")


def compile_cache_dir(checkout: pathlib.Path) -> str:
    """JAX's persistent cache: JAX_COMPILATION_CACHE_DIR where it is set,
    else a fixed directory inside the checkout. Every program is written,
    however quickly it compiles, so that the window loads what the program
    obtains there rather than compiling it; and the cache is not capped,
    since a cap under one configuration's programs (phi4's outgrow 192 MiB)
    evicts the least recently used of them, and they would be compiled
    again in the window or primed again in every run's set-up."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(checkout / ".bench_cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return d
