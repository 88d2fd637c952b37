"""Validates the loop-calibrated cost accounting (flags.py) against a fully
unrolled compile on a small cell, and the HLO collective-byte parser."""
import pytest

from repro import roofline


def test_collective_parser_on_synthetic_hlo():
    hlo = """
  %all-gather.1 = bf16[2048,1024]{1,0} all-gather(bf16[128,1024]{1,0} %p0)
  %all-reduce.7 = f32[4096]{0} all-reduce(f32[4096]{0} %p1), replica_groups={}
  %reduce-scatter.2 = f32[256]{0} reduce-scatter(f32[4096]{0} %p2)
  %all-to-all.9 = s8[64,128]{1,0} all-to-all(s8[64,128]{1,0} %p3)
  %collective-permute.3 = bf16[32,32]{1,0} collective-permute(bf16[32,32]{1,0} %p4)
  %add.1 = f32[10]{0} add(f32[10]{0} %a, f32[10]{0} %b)
"""
    got = roofline.collective_bytes(hlo)
    assert got["all-gather"] == 2048 * 1024 * 2           # result bytes
    assert got["all-reduce"] == 2 * 4096 * 4              # ring 2x
    assert got["reduce-scatter"] == 4096 * 4              # operand larger
    assert got["all-to-all"] == 64 * 128
    assert got["collective-permute"] == 32 * 32 * 2
    assert "add" not in got


def test_peaks_by_device_kind():
    assert roofline.peaks("TPU v5 lite").hbm_bw == roofline.HBM_BW
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("TPU v9 imaginary")


def test_probe_calibration_matches_full_unroll(subproc):
    """base + sum(mult_i * delta_i) == fully-unrolled cost (within 2%)."""
    out = subproc("""
import jax, jax.numpy as jnp
from repro import flags, roofline
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_mesh
from repro.launch import dryrun
from repro.dist import annotate

cfg = get_config("mamba2-780m-smoke")   # has groups + ce + ssd loops
shape = ShapeConfig("t", 64, 8, "train")
mesh = make_mesh((2, 4), ("data", "model"))
annotate.set_batch_axes(("data",))
knobs = dryrun.resolve_variant("precise", cfg)

def measure():
    return dryrun._compile_and_measure(cfg, shape, mesh, knobs, policy="tp",
                                       n_micro=2, remat="full")

flags.reset_unroll()
base = measure()
mults = dryrun.loop_trips(cfg, shape, knobs, 2, "full")
flops = base["flops"]; byts = base["bytes_accessed"]
for site, extra in mults.items():
    flags.reset_unroll(); flags.set_unroll(site, 2)
    p = measure()
    flops += extra * max(p["flops"] - base["flops"], 0.0)
    byts += extra * max(p["bytes_accessed"] - base["bytes_accessed"], 0.0)
# ground truth: unroll every site fully
flags.reset_unroll()
from repro.approx.knobs import keep_groups
from repro.models.lm import ce_chunk
g = len(keep_groups(cfg.n_groups, 0.0))
flags.set_unroll("groups", g)
flags.set_unroll("ce", 64 // ce_chunk(64))
flags.set_unroll("ssd", 64 // cfg.ssm.chunk)
flags.set_unroll("micro", 2)
full = measure()
rel_f = abs(flops - full["flops"]) / full["flops"]
rel_b = abs(byts - full["bytes_accessed"]) / full["bytes_accessed"]
print(f"calibrated {flops:.4e} vs unrolled {full['flops']:.4e} rel {rel_f:.4f}")
print(f"bytes rel {rel_b:.4f}")
assert rel_f < 0.02, rel_f
assert rel_b < 0.05, rel_b
print("CALIBRATION_OK")
""", devices=8, timeout=420)
    assert "CALIBRATION_OK" in out
