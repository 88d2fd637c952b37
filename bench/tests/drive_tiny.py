"""Drives one run of the harness without the look for a chip, optionally
with the timed path broken underneath; prints the result as JSON.

    python bench/tests/drive_tiny.py <fault> <harness arguments>

fault: none | token (a served token altered where it is sampled) |
state (decode leaves the attention pages it should write unchanged).
"""
import json
import pathlib
import sys
import time

T = time.perf_counter()
BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def plant(fault):
    import jax.numpy as jnp
    from repro.models import attention, lm
    if fault == "token":
        sample = lm.sample_token

        def altered(logits, uids, draws, **kw):
            t = sample(logits, uids, draws, **kw)
            return jnp.where(draws % 3 == 1, (t + 1) % logits.shape[-1], t)
        lm.sample_token = altered
    elif fault == "state":
        decode = attention.paged_decode_attention

        def unchanged(params, x, position, cache, *a, **kw):
            y, _ = decode(params, x, position, cache, *a, **kw)
            return y, cache
        attention.paged_decode_attention = unchanged
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault}")


if __name__ == "__main__":
    from harness import main
    fault, argv = sys.argv[1], sys.argv[2:]
    r = main.run(argv, t_start=T, require_chip=False,
                 hook=lambda eng: plant(fault))
    print(json.dumps(r))
