"""Multi-host guard/stop verdict agreement (ISSUE 4 satellite).

Spawns two real OS processes that rendezvous through
``jax.distributed.initialize`` on CPU and drives
``Resilience.sync_verdicts`` with rank-DIVERGENT local verdicts: rank 0
alone accumulates the guard's bad-step streak, then rank 1 alone receives
the preemption stop.  Both ranks must come out of each sync with the SAME
agreed ``(stop, rewind)`` pair — the in-band max-reduce that closes the
ROADMAP cross-host-rewind gap (a host-local flag driving a lockstep
save/restore one-sidedly was the failure mode).
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from deepfake_detection_tpu.train.resilience import allreduce_flags

pytestmark = pytest.mark.smoke

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))

_WORKER = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
rank, coord = int(sys.argv[1]), sys.argv[2]
jax.distributed.initialize(coordinator_address=coord, num_processes=2,
                           process_id=rank)
from deepfake_detection_tpu.train.resilience import (
    AnomalyGuard, PreemptionHandler, Resilience)

res = Resilience(preemption=PreemptionHandler(),
                 guard=AnomalyGuard(rewind_after=2, coordinated=True))
out = {}

# phase 1: only rank 0 sees bad steps; its streak crosses rewind_after but
# the coordinated guard DEFERS the raise (observe returning at all proves it)
for i in range(2):
    bad = rank == 0
    res.guard.observe(i, float("nan") if bad else 1.0, bad)
out["local_rewind_wanted"] = res.guard.rewind_wanted
stop, rewind = res.sync_verdicts()
out["phase1"] = [stop, rewind]
res.guard.reset_streak()

# phase 2: only rank 1 was "signalled"; rank 0 must adopt the stop
if rank == 1:
    res.preemption.stop_requested = True
stop, rewind = res.sync_verdicts()
out["phase2"] = [stop, rewind]
out["stop_adopted"] = res.stop_requested

# phase 3: nothing pending anywhere -> agreed all-clear
res.preemption.stop_requested = False
stop, rewind = res.sync_verdicts()
out["phase3"] = [stop, rewind]
print("RESULT_JSON=" + json.dumps(out), flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def test_allreduce_flags_single_process_identity():
    got = allreduce_flags(np.array([1, 0, 1], np.int32))
    assert got.tolist() == [1, 0, 1]


def test_two_process_verdict_agreement():
    coord = f"localhost:{_free_port()}"
    env = dict(os.environ, PYTHONPATH=_REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(_REPO, ".jax_cache"))
    procs = [
        subprocess.Popen([sys.executable, "-c", _WORKER, str(i), coord],
                         env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True, cwd=_REPO)
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()

    results = []
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {i} failed:\n{out[-4000:]}"
        lines = [ln for ln in out.splitlines()
                 if ln.startswith("RESULT_JSON=")]
        assert lines, f"rank {i} printed no result:\n{out[-2000:]}"
        results.append(json.loads(lines[-1][len("RESULT_JSON="):]))

    r0, r1 = results
    # the streak crossed rewind_after only on rank 0, and only locally
    assert r0["local_rewind_wanted"] is True
    assert r1["local_rewind_wanted"] is False
    for r in results:                       # both ranks agree, each phase
        assert r["phase1"] == [False, True], r
        assert r["phase2"] == [True, False], r
        assert r["stop_adopted"] is True, r
        assert r["phase3"] == [False, False], r
