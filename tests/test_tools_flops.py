"""tools/flops_breakdown.py: the MXU/VPU classification must stay honest."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.smoke

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def _run(*args):
    env = dict(os.environ, PYTHONPATH=_REPO, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "flops_breakdown.py"),
         *args],
        capture_output=True, text=True, env=env, timeout=300, check=True)
    return json.loads(out.stdout)


def test_breakdown_classifies_depthwise_and_dots():
    r = _run("mnasnet_small", "--size", "64")
    # mnasnet has both dense and depthwise convs; totals must be positive
    # and percentages sum to ~100
    assert r["total_gflops_fwd"] > 0
    assert r["conv_depthwise_vpu"]["pct"] > 0
    assert r["conv_dense_mxu"]["pct"] > 0
    pct = sum(v["pct"] for k, v in r.items()
              if isinstance(v, dict) and "pct" in v)
    assert abs(pct - 100.0) < 0.1
    # the stem is split out: a 3-channel 3x3 conv feeding 27 of 128 lanes
    (stem,) = r["stem"]
    assert stem["kernel"] == "3x3x3"
    assert stem["contraction_depth"] == 27
    assert 0.2 < stem["mxu_lane_occupancy"] < 0.22


def test_ceilings_band_and_s2d_reclassification():
    base = _run("mnasnet_small", "--size", "64", "--ceilings")
    c = base["ceilings"]
    # the unfused worst case can only be WORSE than the fused bound, and
    # both are proper fractions
    assert 0 < c["mfu_ceiling_unfused_worst"] \
        < c["mfu_ceiling_post_fusion"] <= 1.0
    assert c["dw_epilogue_extra_mb_per_sample"] > 0

    s2d = _run("mnasnet_small", "--size", "64", "--ceilings", "--stem-s2d")
    (stem,) = s2d["stem"]
    # the s2d stem is reclassified from the flag-built model's own jaxpr:
    # 2x2 kernel over 4C channels, 16/9 the taps of the embedded 3x3
    assert stem["kernel"] == "2x2x12"
    assert stem["contraction_depth"] == 48
    assert s2d["total_gflops_fwd"] >= base["total_gflops_fwd"]
    # MFU stays normalized to the STOCK model's useful FLOPs, so the s2d
    # compute ceiling prices the zero-tap overhead (layout wins are
    # measured, not modeled — PERF.md post-fusion roofline)
    assert s2d["ceilings"]["mfu_ceiling_post_fusion"] \
        <= c["mfu_ceiling_post_fusion"]


def test_gauge_count_needs_no_tools_directory(tmp_path):
    """The MFU gauge's count comes from the package (obs/flops.py): with the
    package alone on the path — no tools/ beside it — it reads what the
    CLI prints, not 0."""
    os.symlink(os.path.join(_REPO, "deepfake_detection_tpu"),
               tmp_path / "deepfake_detection_tpu")
    code = (
        "import jax, sys\n"
        "from deepfake_detection_tpu.models import create_model, init_model\n"
        "from deepfake_detection_tpu.obs import forward_flops_per_sample\n"
        "import deepfake_detection_tpu.obs.telemetry as t\n"
        f"assert t.__file__.startswith({str(tmp_path)!r}), t.__file__\n"
        "m = create_model('mnasnet_small', num_classes=2, in_chans=3)\n"
        "v = jax.eval_shape(lambda: init_model(m, jax.random.PRNGKey(0),"
        " (1, 64, 64, 3)))\n"
        "print(forward_flops_per_sample(m, v, (1, 64, 64, 3)))\n"
        "assert not any('flops_breakdown' in k for k in sys.modules)\n")
    env = dict(os.environ, PYTHONPATH=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         capture_output=True, text=True, env=env,
                         timeout=300, check=True)
    flops = float(out.stdout.strip().splitlines()[-1])
    assert flops > 0
    assert round(flops / 1e9, 2) == \
        _run("mnasnet_small", "--size", "64")["total_gflops_fwd"]
