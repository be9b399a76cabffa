"""The benchmark's own FLOP walk (over the plain reference) against the
program's ``tools/flops_breakdown.py`` walk over the program's model."""
import importlib.util
import os

from benchmark.lib import flops as F
from benchmark.lib import manifest as M


def _program_total(model_name, in_chans, size):
    import jax
    import jax.numpy as jnp
    from deepfake_detection_tpu.models import create_model, init_model
    spec = importlib.util.spec_from_file_location(
        "_fb", os.path.join(M.ROOT, "tools", "flops_breakdown.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    model = create_model(model_name, num_classes=2, in_chans=in_chans)
    v = init_model(model, jax.random.PRNGKey(0), (1, size, size, in_chans))
    buckets, _, dw_out = mod.analyze(
        model, v, jnp.zeros((1, size, size, in_chans)), in_chans)
    return sum(buckets.values()), buckets.get("conv_depthwise_vpu", 0.0), \
        dw_out


def test_tiny_counts_agree_with_the_programs_walk():
    cfg = M.load_json(os.path.join(M.BENCH, "tests", "tiny", "tiny_b0.json"))
    counts = F.forward_counts(cfg)
    total, dw, dw_out = _program_total("efficientnet_b0", 12, 64)
    assert abs(counts["forward_flops"] - total) <= 1e-9 * total
    assert abs(counts["dw_flops"] - dw) <= 1e-9 * dw
    assert counts["dw_out_elems"] == dw_out


def test_flagship_and_b4_forward_gflops():
    """PERF.md's arithmetic rows: 80.5 GF (flagship), 8.78 GF (B4)."""
    for name, want in (("flagship_v4_600", 80.5e9), ("effnet_b4_380", 8.78e9)):
        cfg = M.load_json(os.path.join(M.BENCH, "configs", name + ".json"))
        got = F.forward_counts(cfg)["forward_flops"]
        assert abs(got - want) / want < 0.01, (name, got)


def test_dw_floor_is_bound_by_bytes():
    cfg = M.load_json(os.path.join(M.BENCH, "configs",
                                   "flagship_v4_600.json"))
    peaks = M.load_json(os.path.join(M.BENCH, "lib", "peaks.json"))
    floor = F.dw_train_floor_seconds(F.forward_counts(cfg), 3,
                                     peaks["TPU v5 lite"])
    assert floor["bound"] == "bytes" and floor["seconds"] > 0
