"""tools/bench_blocks.py --smoke: the bench harness itself cannot rot.

One fresh-interpreter run of the full row matrix at seconds-scale shapes;
asserts every row family emits both implementations with sane numbers.
Performance is NOT asserted (CPU, interpreter Pallas) — the doc tables
only admit TPU-stamped rows, which is exactly what the ``interpret`` /
``device`` fields in each row exist to gate.
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.smoke, pytest.mark.pallas]

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


@pytest.mark.slow   # tier-1 budget: subprocess bench smoke (~33s)
def test_bench_blocks_smoke_emits_full_matrix():
    # share the suite's persistent compilation cache (conftest.py): the
    # XLA step/stem programs dominate the smoke's runtime and cache across
    # runs; only the interpret-mode Pallas tracing re-pays every time
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR",
                           os.path.join(_REPO, ".jax_cache"))
    env = dict(os.environ, PYTHONPATH=_REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache)
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "bench_blocks.py"),
         "--smoke"],
        capture_output=True, text=True, env=env, timeout=600, check=True)
    rows = [json.loads(ln) for ln in out.stdout.splitlines() if ln.strip()]
    assert any("note" in r for r in rows)       # CPU rows are flagged

    blocks = [r for r in rows if r.get("row") == "block"]
    assert {r["impl"] for r in blocks} == {"xla", "pallas"}
    assert not any("error" in r for r in blocks), blocks
    for r in blocks:
        assert r["fwd_ms"] > 0 and r["fwd_bwd_ms"] > 0
        # the interpreter stamp gates these rows out of the doc tables
        assert r["interpret"] == (r["impl"] == "pallas")

    stems = [r for r in rows if r.get("row") == "stem"]
    assert {r["impl"] for r in stems} == {"stride2", "s2d"}

    steps = [r for r in rows if r.get("row") == "step"]
    assert {r["impl"] for r in steps} == \
        {"baseline", "fused", "s2d", "fused+s2d"}
    assert not any("error" in r for r in steps), steps


def _tool():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_blocks", os.path.join(_REPO, "tools", "bench_blocks.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("shape,expected", [
    # 32 channels stored in 128 lanes, 300 rows of 8 sublanes
    ("bf16[3,300,300,32]{3,2,1,0:T(8,128)(2,1)}", 3 * 300 * 304 * 128 * 2),
    # the k-fold copy XLA feeds the filter gradient at batch 3
    ("bf16[75,24,10,480,5]{3,1,2,0,4:T(8,128)(2,1)}",
     5 * 75 * 10 * 24 * 512 * 2),
    ("f32[480]{0:T(512)}", 512 * 4),
    ("(f32[5,5,1,480]{3,2,1,0:T(1,128)}, bf16[2,3]{1,0})",
     5 * 5 * 1 * 512 * 4 + 2 * 3 * 2),
])
def test_tiled_bytes(shape, expected):
    assert _tool().tiled_bytes(shape) == expected


def test_aot_bytes_smoke_reads_a_compiled_block(capsys):
    """``--aot-bytes`` under ``--smoke``: the same reading of a compiled
    block's ENTRY operations (bytes by module path, what feeds only the
    depthwise filter gradient), on the CPU backend's text — the harness
    cannot rot; the chip's numbers come from the described-chip run."""
    import argparse
    _tool().aot_bytes(argparse.Namespace(
        aot_bytes="ir,3,9,8,8,3,1", smoke=True, dtype="float32"))
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    assert [r["impl"] for r in rows] == ["xla", "kernel"]
    for r in rows:
        assert r["row"] == "aot_bytes" and r["target"].startswith("cpu")
        assert r["entry_ops"] > 0 and r["all_mb"] > 0
        assert r["by_group_mb"]["conv_dw"] > 0
        assert 0 < r["filter_grad_only_mb"] < r["all_mb"]
        assert r["filter_grad_only_ops"]
