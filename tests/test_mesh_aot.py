"""Abstract-topology AOT acceptance for the unified GSPMD train step.

ISSUE 12 hard criterion: on this CPU box, the train step must LOWER AND
COMPILE for mesh shapes (1,1), (8,1), (16,4), (64,4) — one chip up to a
v5e-256 pod slice — with every TrainState leaf carrying its intended
PartitionSpec and state donation preserved, all asserted from the
compiled executable's input/output shardings.

One fresh subprocess (tools/bench_multichip.py parent mode) forces 256
virtual CPU devices and runs the whole matrix; this test consumes its
JSON verdict.  The step it compiles is the runner's own
(``runners/train.py:build_program`` → ``build_steps`` on each topology's
mesh), and the tool is the same thing the verify recipe smokes.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ACCEPTANCE_SHAPES = [[1, 1], [8, 1], [16, 4], [64, 4]]


@pytest.fixture(scope="module")
def matrix(tmp_path_factory):
    out = tmp_path_factory.mktemp("aot") / "matrix.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = ""
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
        REPO, ".jax_cache"))
    # the tool's own child budget must be SHORTER than this subprocess
    # timeout, so a wedged compile surfaces as the tool's structured
    # failure instead of pytest killing the parent and orphaning the
    # compiling grandchild
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_multichip.py"),
         "--shapes", "1x1,8x1,16x4,64x4", "--timeout", "360",
         "--out", str(out)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, \
        f"rc={r.returncode}\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    with open(out) as f:
        return json.load(f)


def test_all_acceptance_topologies_compile(matrix):
    got = [r["mesh_shape"] for r in matrix["rows"] if not r["fsdp"]]
    assert got == ACCEPTANCE_SHAPES, got
    for row in matrix["rows"]:
        # the step lowered AND compiled (wall-times recorded per topology)
        assert row["lower_s"] > 0 and row["compile_s"] > 0, row
        assert row["hlo_bytes"] > 0, row


def test_fsdp_row_proves_nontrivial_specs(matrix):
    """The spec assertion must not be vacuous: the fsdp row carries
    genuinely sharded TrainState leaves (params + their moments/EMA) and
    the compiled executable still honors every one of them."""
    fsdp_rows = [r for r in matrix["rows"] if r["fsdp"]]
    assert len(fsdp_rows) == 1
    row = fsdp_rows[0]
    assert row["sharded_leaves"] > 0, row
    assert row["specs_ok"] and row["donation_preserved"], row


def test_every_state_leaf_keeps_its_partition_spec(matrix):
    for row in matrix["rows"]:
        assert row["specs_ok"], (row["mesh_shape"], row["spec_misses"])
        assert row["state_leaves"] > 0


def test_state_donation_survives_every_topology(matrix):
    for row in matrix["rows"]:
        assert row["donation_preserved"], row["mesh_shape"]


def test_matrix_verdict_is_green(matrix):
    assert matrix["ok"] is True
    assert matrix["kind"] == "abstract_mesh_aot"
