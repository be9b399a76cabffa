"""tools/bench_input.py: the host-pipeline benchmark must keep working."""

import os
import sys
from types import SimpleNamespace

import pytest

pytestmark = pytest.mark.smoke

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

from tools import bench_input  # noqa: E402


def test_build_and_measure(tmp_path):
    root = str(tmp_path / "clips")
    os.makedirs(root)
    bench_input.build_dataset(root, n_clips=6, size=64, frames=4)
    assert os.path.isfile(os.path.join(root, "fake_list.txt"))
    args = SimpleNamespace(clips=6, size=64, frames=4, batch=2, workers=1,
                           epochs=1)
    # finally + plain pop, NOT monkeypatch.delenv: monkeypatch RESTORES
    # the var at teardown (measure(native=False) set it mid-test), which
    # silently disabled the native path for every later test; a bare pop
    # after the asserts would leak it on failure instead
    try:
        native_cps = bench_input.measure(root, args, native=True)
        pil_cps = bench_input.measure(root, args, native=False)
        ref_cps = bench_input.measure(root, args, native=False, fast=False)
        assert native_cps > 0 and pil_cps > 0 and ref_cps > 0
    finally:
        os.environ.pop("DFD_NO_NATIVE_DECODE", None)


def test_measure_shm_backend(tmp_path):
    """--backend shm drives the multi-process ring loader through the same
    harness (and tears its workers/segment down afterwards)."""
    root = str(tmp_path / "clips")
    os.makedirs(root)
    bench_input.build_dataset(root, n_clips=4, size=48, frames=4)
    args = SimpleNamespace(clips=4, size=32, frames=4, batch=2, workers=2,
                           epochs=1)
    try:
        cps = bench_input.measure(root, args, native=True, backend="shm")
        assert cps > 0
    finally:
        os.environ.pop("DFD_NO_NATIVE_DECODE", None)


def test_packed_matrix_smoke(tmp_path):
    """--packed matrix: packs the synthetic set, measures decode vs packed
    (fetch + both chains), emits backend=packed provenance rows, and the
    budget gate skips rows with <60s left instead of starting them."""
    import json
    root = str(tmp_path / "clips")
    os.makedirs(root)
    bench_input.build_dataset(root, n_clips=6, size=40, frames=4)
    out = str(tmp_path / "rows.jsonl")
    args = SimpleNamespace(clips=6, size=32, frames=4, batch=2, workers=2,
                           epochs=1, budget=0.0, json=out)
    rows = bench_input.run_packed(root, args)
    packed_rows = [r for r in rows if r["backend"] == "packed"]
    assert {r["row"] for r in rows} == {"fetch", "eval", "train"}
    assert len(packed_rows) == 3
    assert all(r["clips_per_s"] > 0 for r in rows)
    with open(out) as f:
        emitted = [json.loads(line) for line in f]
    assert sum(r.get("backend") == "packed" for r in emitted) == 3
    # an exhausted budget records skips, never starts a row
    args2 = SimpleNamespace(clips=6, size=32, frames=4, batch=2, workers=2,
                            epochs=1, budget=0.001, json="")
    rows2 = bench_input.run_packed(root, args2)
    assert rows2 and all("skipped" in r for r in rows2)


def test_device_augment_matrix_smoke(tmp_path):
    """--device-augment matrix: host-augment vs passthrough rows on both
    transports (packed source), provenance-stamped, budget gate honored."""
    import json
    root = str(tmp_path / "clips")
    os.makedirs(root)
    bench_input.build_dataset(root, n_clips=6, size=40, frames=4)
    out = str(tmp_path / "rows.jsonl")
    args = SimpleNamespace(clips=6, size=32, frames=4, batch=2, workers=2,
                           epochs=1, budget=0.0, json=out, e2e=False)
    rows = bench_input.run_device_augment(root, args)
    assert {r["row"] for r in rows} == {
        "host-augment/thread", "device-augment/thread",
        "host-augment/shm", "device-augment/shm"}
    assert all(r["clips_per_s"] > 0 and r["source"] == "packed"
               for r in rows)
    # no wall-clock ordering assert: a single 3-batch toy measurement under
    # CI load can invert; the measured ratios live in INPUT_BENCH.md
    with open(out) as f:
        emitted = [json.loads(line) for line in f]
    assert sum(r.get("kind") == "device_augment" for r in emitted) == 4
    args2 = SimpleNamespace(clips=6, size=32, frames=4, batch=2, workers=2,
                            epochs=1, budget=0.001, json="", e2e=False)
    rows2 = bench_input.run_device_augment(root, args2)
    assert rows2 and all("skipped" in r for r in rows2)


def test_gil_pause_methodology():
    """tools/bench_gil.py: the PyDLL control must read as GIL-held and the
    production CDLL decode as GIL-free — the measured basis for
    INPUT_BENCH.md's linear thread-scaling extrapolation."""
    from deepfake_detection_tpu.data import native
    if not native.available():
        pytest.skip("native lib unavailable")
    import json
    import subprocess
    for _ in range(3):
        r = subprocess.run(
            [sys.executable, os.path.join(os.path.dirname(__file__),
                                          os.pardir, "tools", "bench_gil.py"),
             "--src", "2200", "--reps", "2"],
            capture_output=True, text=True, timeout=240)
        assert r.returncode == 0, r.stderr[-500:]
        parsed = [json.loads(l) for l in r.stdout.splitlines()
                  if l.startswith("{")]
        errors = [j for j in parsed if "error" in j]
        assert not errors, (errors, r.stderr[-300:])
        rows = {j["stage"]: j for j in parsed if "stage" in j}
        # None = the tool could not tell (its spinner was starved by the
        # other workers of a loaded suite): measure again, never guess
        if all(j["gil_held"] is not None for j in rows.values()):
            break
    assert rows["control_warp_PyDLL_gil_held"]["gil_held"] is True
    assert rows["decode_native_CDLL"]["gil_held"] is False
    assert rows["warp_native_CDLL"]["gil_held"] is False
