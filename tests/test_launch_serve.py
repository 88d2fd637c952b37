"""The serving CLI's builder (``launch.serve.serve``), its exit code, and the
entry points' compile-cache placement."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import compile_cache, serve as serve_mod

ARGV = ["--arch", "phi4-mini-3.8b-smoke", "--megastep", "4",
        "--page-size", "4", "--slots", "2", "--requests", "3",
        "--max-len", "32", "--prompt-len", "8", "--prefill-chunk", "4",
        "--max-new", "5"]


def test_serve_builds_bf16_and_finishes_every_request():
    seen = []
    served = serve_mod.serve(ARGV, on_step=lambda eng, step: seen.append(step))
    s = served.summary
    assert s["done"] == s["requests"] == 3 and s["unfinished"] == []
    assert all(len(r.out) == 5 for r in served.requests)
    assert seen == list(range(1, s["steps"] + 1))
    eng = served.engine
    assert {x.dtype for x in jax.tree.leaves(eng.params)} == {
        jnp.dtype(jnp.bfloat16)}
    assert eng.caches[0].kp.dtype == jnp.bfloat16
    assert served.engine.table.target == "TPU v5 lite"


def test_serve_depth_cut_and_float32():
    served = serve_mod.serve(ARGV + ["--layers", "1", "--dtype", "float32",
                                     "--requests", "1"])
    eng = served.engine
    assert eng.cfg.n_layers == 1
    assert eng.caches[0].kp.dtype == jnp.float32
    assert served.summary["done"] == 1


@pytest.mark.parametrize("unfinished,rc", [([], 0), ([2], 1)])
def test_main_exit_code(monkeypatch, unfinished, rc):
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a, **k: None)
    monkeypatch.setattr(serve_mod, "serve", lambda argv: serve_mod.Served(
        None, [], {"unfinished": unfinished}))
    assert serve_mod.main([]) == rc


def test_compile_cache_dir(monkeypatch):
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    d = compile_cache.use_compile_cache()
    assert d == str(compile_cache.REPO / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", d)]
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    calls.clear()
    assert compile_cache.use_compile_cache() == "/elsewhere"
    assert calls == []
