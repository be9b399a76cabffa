#!/usr/bin/env python
"""Closed-loop chaos harness for the serve/stream stack (ISSUE 10).

Where ``tools/chaos.py`` proves the TRAINING recovery contract
(inject fault → assert exit code → auto-resume → bit-identical state),
this harness proves the SERVING one: it spawns a live
``runners/serve.py`` / ``runners/stream.py`` with a ``DFD_CHAOS`` fault
armed, drives it with real HTTP load, watches the fault fire in
/metrics, and asserts the recovery invariants:

* **books balance** — ``accepted == cache_hit + scored + shed +
  deadline + failed`` from a post-drain /metrics scrape, exactly: no
  request is ever lost or double-counted through a fault (with
  ``--cache-entries`` the serve scenarios run the verdict cache live,
  so hits flow through the fault window too);
* **zero post-recovery recompiles** — ``backend_compiles_total`` (jax's
  own monitoring hook) does not move across fault + recovery: re-warms
  execute existing bucket executables;
* **recovery SLO** — from the first fault-induced failure to the next
  successful score is bounded (``--slo-s``);
* **no verdict-stream resets** — a SIGTERM'd stream server restarted
  with the same ``--state-dir`` resumes per-stream verdict machines and
  finishes BIT-IDENTICALLY (status + events) to an unkilled replay.

Scenarios (``--scenario``, comma list or ``all``):

* ``exc``          — score-fn exception mid-traffic (``serve_exc``);
* ``nan``          — non-finite device scores (``serve_nan``): riders
  get 503, ``nonfinite_batches_total`` moves, next batch serves;
* ``hang``         — artificial device hang (``serve_hang``): the
  stuck-batch watchdog fails in-flight requests, restarts the worker
  and re-warms buckets (readiness dips, then serving resumes);
* ``kill``         — engine worker killed outright (``serve_kill``):
  the watchdog's liveness probe respawns it;
* ``torn_reload``  — the reload watcher is fed a half-truncated
  checkpoint copy (``torn_reload``): rejected loudly, scores
  bit-identical before/after, the clean file reloads on the next tick;
* ``stream_resume``— stream server SIGTERM + restart with
  ``--state-dir``: verdict streams RESUME (compared against an
  unkilled replay of the same frames).
* ``replica_kill`` — fleet scenario (ISSUE 15): 2 serve replicas behind
  ``runners/router.py``, one SIGKILLed under load — the router fails
  over within ``--slo-s``, books stay exact (routed == forwarded +
  migrated + shed + failed), and a relaunch on the same port rejoins
  the rotation;
* ``replica_migrate`` — fleet scenario: a live stream's replica is
  DRAINED — the session snapshot/restores onto the peer via the PR 10
  state machinery, the stream finishes through the router, and the
  final status + event log are BIT-IDENTICAL to an undrained replay.
* ``fleet_elastic`` — autoscaler scenario (ISSUE 18): 1 replica + the
  SLO autoscaler + the backfill tenant on the idle slot; a spike makes
  the tenant YIELD (SIGTERM → exit-75 lease release) and scale-up
  spawn into its slot, the new warming replica is SIGKILLed and
  respawned under load, then scale-in drains back to the floor and the
  tenant runs the corpus dry — exact books on BOTH tenants, zero
  client-visible failures, zero post-transition recompiles, bit-exact
  decision-trace replay.

Example (the CI slow tier runs exactly this, small model)::

    JAX_PLATFORMS=cpu \
        python tools/chaos_serve.py --scenario all \
        --model mobilenetv3_small_100 --image-size 32
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from tools.bench_serve import assert_router_books, free_port, \
    labeled_family, make_jpegs, scrape_metrics, scrape_metrics_labeled, \
    spawn_router, wait_fleet_ready, wait_ready  # noqa: E402

SCENARIOS = ("exc", "nan", "hang", "kill", "torn_reload", "stream_resume",
             "replica_kill", "replica_migrate", "fleet_elastic")


def _log(msg: str) -> None:
    print(f"[chaos_serve] {msg}", file=sys.stderr, flush=True)


def _child_env(chaos: str = "") -> dict:
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    if chaos:
        env["DFD_CHAOS"] = chaos
    else:
        env.pop("DFD_CHAOS", None)
    return env


def _terminate(proc: subprocess.Popen, timeout: float = 15.0) -> int:
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=timeout)
    return proc.returncode


# ---------------------------------------------------------------------------
# serve-side scenarios
# ---------------------------------------------------------------------------

def _spawn_serve(args, port: int, chaos: str,
                 extra: Optional[List[str]] = None) -> subprocess.Popen:
    cmd = [sys.executable, "-m", "deepfake_detection_tpu.runners.serve",
           "--model", args.model, "--image-size", str(args.image_size),
           "--img-num", "1", "--port", str(port), "--buckets", "1,4",
           "--batch-deadline-ms", "5", "--max-queue", "64",
           "--watchdog-timeout-s", str(args.watchdog_timeout_s),
           "--breaker-threshold", str(args.breaker_threshold)]
    if getattr(args, "cache_entries", 0):
        # verdict cache live through the fault (ISSUE 17): the poster
        # cycles few distinct jpegs, so hits flow during the fault
        # window and the books identity is asserted WITH its cache term
        cmd += ["--cache-entries", str(args.cache_entries)]
    if args.models:
        # two-model mode (ISSUE 14): every serve scenario runs with the
        # extra model(s) loaded — recovery re-warms BOTH models' buckets,
        # books must balance across the whole table; --cascade routes
        # the load student-first so faults hit cascade traffic too
        cmd += ["--models", args.models]
        if args.cascade:
            cmd += ["--cascade", args.cascade,
                    "--cascade-low", "0.0", "--cascade-high", "1.0"]
    cmd += list(extra or [])
    _log("spawn: DFD_CHAOS=%r %s" % (chaos, " ".join(cmd)))
    return subprocess.Popen(cmd, cwd=_REPO, env=_child_env(chaos),
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)


class _Poster(threading.Thread):
    """Modest closed-loop poster: keeps batches flowing so stepped chaos
    points fire, records (t, status) samples for the SLO computation."""

    def __init__(self, netloc: str, jpegs: List[bytes],
                 stop: threading.Event):
        super().__init__(daemon=True)
        host, port = netloc.split(":")
        self.host, self.port = host, int(port)
        self.jpegs = jpegs
        self.stop_ev = stop
        self.samples: List[Tuple[float, int]] = []

    def run(self) -> None:
        conn = None
        i = 0
        while not self.stop_ev.is_set():
            try:
                if conn is None:
                    conn = http.client.HTTPConnection(
                        self.host, self.port, timeout=30)
                body = self.jpegs[i % len(self.jpegs)]
                i += 1
                conn.request("POST", "/score", body,
                             {"Content-Type": "image/jpeg"})
                resp = conn.getresponse()
                resp.read()
                status = resp.status
            except OSError:
                if conn is not None:
                    conn.close()
                conn = None
                status = -1
            self.samples.append((time.monotonic(), status))
            if status in (429, 503):
                self.stop_ev.wait(0.05)   # fast probe cadence: the SLO
                # measurement wants a tight upper bound on recovery
        if conn is not None:
            conn.close()


def _drive_until_recovered(netloc: str, jpegs: List[bytes],
                           fault_seen, slo_s: float,
                           concurrency: int = 3,
                           timeout_s: float = 120.0) -> Dict[str, float]:
    """Post load until ``fault_seen()`` is true AND a later 200 lands;
    returns fault/recovery timing + status counts."""
    stop = threading.Event()
    posters = [_Poster(netloc, jpegs, stop) for _ in range(concurrency)]
    for p in posters:
        p.start()
    t0 = time.monotonic()
    fault_t = None
    recovered_t = None
    try:
        while time.monotonic() - t0 < timeout_s:
            if fault_t is None:
                if fault_seen():
                    fault_t = time.monotonic()
                    _log(f"fault observed after {fault_t - t0:.1f}s")
            else:
                ok = [t for p in posters for (t, s) in list(p.samples)
                      if s == 200 and t > fault_t]
                if ok:
                    recovered_t = min(ok)
                    break
            time.sleep(0.05)
    finally:
        stop.set()
        for p in posters:
            p.join(timeout=10)
    if fault_t is None:
        raise AssertionError("fault never observed under load")
    if recovered_t is None:
        raise AssertionError("no successful score after the fault "
                             f"within {timeout_s}s")
    statuses: Dict[int, int] = {}
    for p in posters:
        for _, s in p.samples:
            statuses[s] = statuses.get(s, 0) + 1
    recovery_s = recovered_t - fault_t
    _log(f"recovered {recovery_s:.2f}s after the fault "
         f"(statuses {statuses})")
    if recovery_s > slo_s:
        raise AssertionError(
            f"recovery took {recovery_s:.2f}s > SLO {slo_s}s")
    return {"recovery_s": recovery_s, "statuses": statuses}


def _assert_books_balance(netloc: str, settle_s: float = 2.0) -> dict:
    """Post-drain scrape: accepted == cache_hit + scored + shed +
    deadline + failed, exactly."""
    deadline = time.monotonic() + 30.0
    while True:
        m = scrape_metrics(netloc)
        acc = m.get("dfd_serving_accepted_total", 0)
        resolved = (m.get("dfd_serving_cache_hit_total", 0) +
                    m.get("dfd_serving_scored_total", 0) +
                    m.get("dfd_serving_shed_total", 0) +
                    m.get("dfd_serving_deadline_total", 0) +
                    m.get("dfd_serving_failed_total", 0))
        if acc == resolved or time.monotonic() > deadline:
            break
        time.sleep(settle_s)   # something still in flight: let it drain
    if acc != resolved:
        raise AssertionError(
            f"books do not balance: accepted {acc:.0f} != cache_hit "
            f"{m.get('dfd_serving_cache_hit_total', 0):.0f} + scored "
            f"{m.get('dfd_serving_scored_total', 0):.0f} + shed "
            f"{m.get('dfd_serving_shed_total', 0):.0f} + deadline "
            f"{m.get('dfd_serving_deadline_total', 0):.0f} + failed "
            f"{m.get('dfd_serving_failed_total', 0):.0f}")
    _log(f"books balance: accepted {acc:.0f} == cache_hit "
         f"{m.get('dfd_serving_cache_hit_total', 0):.0f} + "
         f"{resolved - m.get('dfd_serving_cache_hit_total', 0):.0f} "
         f"scored/shed/deadline/failed")
    tri = m.get("dfd_serving_cascade_triaged_total", 0)
    clr = m.get("dfd_serving_cascade_cleared_total", 0)
    esc = m.get("dfd_serving_cascade_escalated_total", 0)
    fs = m.get("dfd_serving_cascade_flagship_scored_total", 0)
    ef = m.get("dfd_serving_cascade_escalation_failed_total", 0)
    if tri or esc:
        # cascade mode: the triage books must hold through the fault too
        if tri != clr + esc or esc != fs + ef:
            raise AssertionError(
                f"cascade books do not balance: {tri:.0f} triaged != "
                f"{clr:.0f} cleared + {esc:.0f} escalated, or {esc:.0f} "
                f"escalated != {fs:.0f} flagship + {ef:.0f} failed")
        _log(f"cascade books balance: {tri:.0f} == {clr:.0f} + {esc:.0f};"
             f" {esc:.0f} == {fs:.0f} + {ef:.0f}")
    return m


def _fault_metric_seen(netloc: str, metric: str, baseline: float = 0.0):
    def probe() -> bool:
        try:
            return scrape_metrics(netloc).get(metric, 0) > baseline
        except OSError:
            return False
    return probe


#: scenario -> (chaos spec, /metrics counter that proves the fault fired;
#: None = the injected exception shows as failed requests)
_SERVE_FAULTS = {
    "exc": ("serve_exc@3", None),
    "nan": ("serve_nan@3",
            "dfd_serving_nonfinite_batches_total"),
    "hang": ("serve_hang@3:20",
             "dfd_serving_watchdog_recoveries_total"),
    "kill": ("serve_kill@3",
             "dfd_serving_watchdog_recoveries_total"),
}


def run_serve_fault(args, name: str) -> dict:
    chaos, metric = _SERVE_FAULTS[name]
    jpegs = make_jpegs(8, args.src_size)
    port = free_port()
    proc = _spawn_serve(args, port, chaos)
    netloc = f"127.0.0.1:{port}"
    try:
        wait_ready(netloc, timeout=args.ready_timeout_s)
        m0 = scrape_metrics(netloc)
        backend0 = m0.get("dfd_serving_backend_compiles_total", 0)
        if metric is None:
            probe = _fault_metric_seen(netloc, "dfd_serving_failed_total")
        else:
            probe = _fault_metric_seen(netloc, metric,
                                       m0.get(metric, 0))
        r = _drive_until_recovered(netloc, jpegs, probe, args.slo_s)
        m1 = _assert_books_balance(netloc)
        backend1 = m1.get("dfd_serving_backend_compiles_total", 0)
        if backend1 != backend0:
            raise AssertionError(
                f"{backend1 - backend0:+.0f} backend recompiles across "
                f"fault + recovery (must be zero)")
        _log(f"{name}: zero post-recovery recompiles "
             f"({backend1:.0f} total)")
        return {"scenario": name, "recovery_s": r["recovery_s"],
                "statuses": r["statuses"],
                "metrics": {k: v for k, v in m1.items()
                            if k.startswith("dfd_serving_")}}
    finally:
        _terminate(proc)


# ---------------------------------------------------------------------------
# torn reload
# ---------------------------------------------------------------------------

def run_torn_reload(args) -> dict:
    """Arm ``torn_reload@0``: the FIRST reload attempt reads a torn copy
    (rejected loudly, serving scores bit-identical), the next tick loads
    the clean file and the reload lands."""
    import numpy as np

    jpegs = make_jpegs(2, args.src_size)
    port = free_port()
    reload_dir = tempfile.mkdtemp(prefix="chaos-reload-")
    proc = _spawn_serve(args, port, "torn_reload@0",
                        extra=["--reload-dir", reload_dir,
                               "--reload-interval-s", "0.3"])
    netloc = f"127.0.0.1:{port}"
    try:
        wait_ready(netloc, timeout=args.ready_timeout_s)

        def score(body: bytes) -> list:
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=30)
            conn.request("POST", "/score", body,
                         {"Content-Type": "image/jpeg"})
            resp = conn.getresponse()
            out = json.loads(resp.read())
            conn.close()
            assert resp.status == 200, out
            return out["scores"]

        s_before = score(jpegs[0])
        # build a compatible checkpoint: same model, nudged params (the
        # server with no --model-path serves the PRNGKey(0) init)
        import jax
        import jax.numpy as jnp
        from deepfake_detection_tpu.models import create_model, init_model
        from deepfake_detection_tpu.models.helpers import \
            save_model_checkpoint
        model = create_model(args.model, num_classes=2, in_chans=3)
        variables = init_model(
            model, jax.random.PRNGKey(0),
            (1, args.image_size, args.image_size, 3))
        rng = np.random.default_rng(7)
        nudged = jax.tree.map(
            lambda a: np.asarray(a) + 0.05 * rng.standard_normal(
                np.shape(a)).astype(np.asarray(a).dtype)
            if np.issubdtype(np.asarray(a).dtype, np.floating)
            else np.asarray(a), variables)
        save_model_checkpoint(os.path.join(reload_dir, "new.msgpack"),
                              nudged)
        # phase 1: the torn copy is rejected; scores stay bit-identical
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            m = scrape_metrics(netloc)
            if m.get("dfd_serving_reload_errors_total", 0) >= 1:
                break
            time.sleep(0.2)
        else:
            raise AssertionError("torn reload was never rejected")
        s_torn = score(jpegs[0])
        if s_torn != s_before:
            raise AssertionError(
                f"scores drifted across a REJECTED reload: {s_before} "
                f"-> {s_torn}")
        _log("torn reload rejected; scores bit-identical")
        # phase 2: next tick reloads the clean file
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            m = scrape_metrics(netloc)
            if m.get("dfd_serving_reloads_total", 0) >= 1:
                break
            time.sleep(0.2)
        else:
            raise AssertionError("clean reload never landed after the "
                                 "torn rejection")
        s_after = score(jpegs[0])
        if s_after == s_before:
            raise AssertionError("reload landed but scores unchanged "
                                 "(nudged weights must move them)")
        _log("clean reload landed on the next tick; scores moved")
        m1 = _assert_books_balance(netloc)
        return {"scenario": "torn_reload",
                "reload_errors": m1.get(
                    "dfd_serving_reload_errors_total", 0),
                "reloads": m1.get("dfd_serving_reloads_total", 0)}
    finally:
        _terminate(proc)


# ---------------------------------------------------------------------------
# stream resume
# ---------------------------------------------------------------------------

def _stream_cmd(args, port: int, state_dir: str, event_dir: str) -> list:
    return [sys.executable, "-m",
            "deepfake_detection_tpu.runners.stream",
            "--model", args.model, "--image-size", str(args.image_size),
            "--img-num", "2", "--port", str(port), "--buckets", "1,4",
            "--max-inflight-windows", "16", "--stream-ttl-s", "0",
            "--verdict-vector", "0.1*3,0.95*17",
            "--state-dir", state_dir, "--event-log-dir", event_dir]


class _StreamClient:
    def __init__(self, port: int):
        self.port = port

    def _req(self, method: str, path: str, body: bytes = b"",
             headers: Optional[dict] = None) -> Tuple[int, dict]:
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=30)
        conn.request(method, path, body, headers or {})
        resp = conn.getresponse()
        out = json.loads(resp.read() or b"{}")
        conn.close()
        return resp.status, out

    def open(self, sid: str) -> None:
        status, out = self._req("POST", "/streams",
                                json.dumps({"stream_id": sid}).encode(),
                                {"Content-Type": "application/json"})
        assert status == 201, (status, out)

    def push_raw(self, sid: str, frames) -> dict:
        import numpy as np
        body = np.concatenate([f.reshape(-1) for f in frames]).tobytes()
        h, w = frames[0].shape[:2]
        status, out = self._req(
            "POST", f"/streams/{sid}/frames", body,
            {"Content-Type": "application/x-dfd-raw",
             "X-Frame-Width": str(w), "X-Frame-Height": str(h)})
        assert status == 200, (status, out)
        return out

    def status(self, sid: str) -> dict:
        status, out = self._req("GET", f"/streams/{sid}")
        assert status == 200, (status, out)
        return out

    def wait_scored(self, sid: str, n: int, timeout: float = 60.0) -> dict:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            st = self.status(sid)
            if st["counters"]["windows_scored"] >= n:
                return st
            time.sleep(0.1)
        raise AssertionError(
            f"stream {sid}: only "
            f"{self.status(sid)['counters']['windows_scored']}/{n} "
            f"windows scored within {timeout}s")


def _strip_wall_time(events: list) -> list:
    return [{k: v for k, v in ev.items() if k != "wall_time"}
            for ev in events]


def _comparable(st: dict) -> dict:
    """The resume-vs-replay comparison view of a stream status: verdict
    machines, counters and event sequence; wall-clock fields dropped."""
    return {
        "verdict": st["verdict"],
        "stream": st["stream"],
        "tracks": st["tracks"],
        "counters": st["counters"],
        "events": _strip_wall_time(st["events"]),
    }


def run_stream_resume(args) -> dict:
    """SIGTERM a stream server mid-stream, restart it on the same
    --state-dir, finish the stream, and require the final status to be
    BIT-IDENTICAL to an unkilled replay of the same frames."""
    import numpy as np
    rng = np.random.default_rng(11)
    s = args.image_size
    # frames sized to the canvas: full_frame localizer + no resize =
    # deterministic pipeline; scores are planted via --verdict-vector
    frames = [rng.integers(0, 255, (s, s, 3), dtype=np.uint8)
              for _ in range(20)]
    # img_num=2, stride 1, default hop -> one window per 2 frames
    phase1, phase2 = frames[:8], frames[8:]
    n1, n_total = len(phase1) // 2, len(frames) // 2

    def drive(client: _StreamClient, sid: str, chunk) -> dict:
        client.push_raw(sid, chunk)
        return client.status(sid)

    state_dir = tempfile.mkdtemp(prefix="chaos-stream-state-")
    event_dir = tempfile.mkdtemp(prefix="chaos-stream-events-")
    port = free_port()
    netloc = f"127.0.0.1:{port}"
    # --- killed + resumed run ---------------------------------------
    proc = subprocess.Popen(_stream_cmd(args, port, state_dir, event_dir),
                            cwd=_REPO, env=_child_env(),
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    try:
        wait_ready(netloc, timeout=args.ready_timeout_s)
        client = _StreamClient(port)
        client.open("resume-me")
        client.push_raw("resume-me", phase1)
        client.wait_scored("resume-me", n1)   # quiesce: nothing in flight
        _log(f"phase 1: {n1} windows scored; SIGTERM")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=30)
        _log(f"server exited {rc}")
    except BaseException:
        _terminate(proc)
        raise
    # --- restart on the same state dir ------------------------------
    port2 = free_port()
    netloc2 = f"127.0.0.1:{port2}"
    proc2 = subprocess.Popen(
        _stream_cmd(args, port2, state_dir, event_dir),
        cwd=_REPO, env=_child_env(), stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    try:
        wait_ready(netloc2, timeout=args.ready_timeout_s)
        m = scrape_metrics(netloc2)
        if m.get("dfd_streaming_streams_restored_total", 0) != 1:
            raise AssertionError("restarted server did not restore the "
                                 "stream snapshot")
        client2 = _StreamClient(port2)
        st_resumed = client2.status("resume-me")
        if st_resumed["counters"]["windows_scored"] != n1:
            raise AssertionError(
                f"verdict stream RESET across the bounce: "
                f"{st_resumed['counters']['windows_scored']} != {n1}")
        client2.push_raw("resume-me", phase2)
        final_resumed = client2.wait_scored("resume-me", n_total)
        proc2.send_signal(signal.SIGTERM)
        proc2.wait(timeout=30)
    except BaseException:
        _terminate(proc2)
        raise
    # --- unkilled replay --------------------------------------------
    port3 = free_port()
    replay_state = tempfile.mkdtemp(prefix="chaos-stream-replay-")
    replay_events = tempfile.mkdtemp(prefix="chaos-stream-replay-ev-")
    proc3 = subprocess.Popen(
        _stream_cmd(args, port3, replay_state, replay_events),
        cwd=_REPO, env=_child_env(), stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    try:
        wait_ready(f"127.0.0.1:{port3}", timeout=args.ready_timeout_s)
        client3 = _StreamClient(port3)
        client3.open("resume-me")
        client3.push_raw("resume-me", phase1)
        client3.wait_scored("resume-me", n1)
        client3.push_raw("resume-me", phase2)
        final_replay = client3.wait_scored("resume-me", n_total)
    finally:
        _terminate(proc3)
    got, want = _comparable(final_resumed), _comparable(final_replay)
    if got != want:
        raise AssertionError(
            "resumed stream diverged from the unkilled replay:\n"
            f"resumed: {json.dumps(got, sort_keys=True)}\n"
            f"replay:  {json.dumps(want, sort_keys=True)}")
    _log(f"stream resume bit-identical to unkilled replay "
         f"(verdict {got['verdict']!r}, "
         f"{got['counters']['windows_scored']} windows)")
    # the per-stream event log must be ONE coherent stream: every line
    # parses, and the transition path is connected across the bounce
    log_path = os.path.join(event_dir, "resume-me.events.jsonl")
    with open(log_path) as f:
        events = [json.loads(line) for line in f]
    # stream-scope and per-track machines interleave in the log: the
    # connected-path invariant holds per machine
    by_machine: Dict[tuple, list] = {}
    for ev in events:
        by_machine.setdefault(
            (ev.get("scope"), ev.get("track_id")), []).append(ev)
    for key, evs in by_machine.items():
        if not all(a["to"] == b["from"] for a, b in zip(evs, evs[1:])):
            raise AssertionError(f"event log transition path for "
                                 f"{key} is broken across the bounce: "
                                 f"{evs}")
    _log(f"event log coherent across the bounce ({len(events)} "
         f"transition(s))")
    return {"scenario": "stream_resume",
            "windows_scored": got["counters"]["windows_scored"],
            "verdict": got["verdict"], "events": len(events)}


# ---------------------------------------------------------------------------
# fleet scenarios (ISSUE 15): replicas behind runners/router.py
# ---------------------------------------------------------------------------

def _spawn_fleet_serve(args, n: int) -> Tuple[list, subprocess.Popen, str]:
    """n serve replicas + router; returns ([(proc, port)...], router_proc,
    router_netloc) with the whole fleet scraped ready."""
    replicas = []
    for _ in range(n):
        port = free_port()
        replicas.append((_spawn_serve(args, port, ""), port))
    for _, port in replicas:
        wait_ready(f"127.0.0.1:{port}", timeout=args.ready_timeout_s)
    router_proc, router_netloc = spawn_router(
        [f"127.0.0.1:{port}" for _, port in replicas],
        data_plane=args.data_plane)
    wait_fleet_ready(router_netloc, n, timeout=args.ready_timeout_s)
    return replicas, router_proc, router_netloc


def run_replica_kill(args) -> dict:
    """SIGKILL one replica of a 2-replica fleet under load: the router
    must fail traffic over to the survivor within --slo-s, books stay
    exact (routed == forwarded + migrated + shed + failed), and a
    relaunched replica on the same port rejoins the rotation."""
    jpegs = make_jpegs(8, args.src_size)
    replicas, router_proc, netloc = _spawn_fleet_serve(args, 2)
    victim_proc, victim_port = replicas[0]
    try:
        # fault probe: the scraper marks the victim down (ready_replicas
        # gauge drops below 2)
        def fault_seen() -> bool:
            try:
                m = scrape_metrics(netloc)
                return m.get("dfd_router_ready_replicas", 2) < 2
            except OSError:
                return False

        killed = threading.Event()

        def killer() -> None:
            time.sleep(1.5)           # let load flow through both first
            _log(f"SIGKILL replica on port {victim_port}")
            victim_proc.kill()
            killed.set()

        threading.Thread(target=killer, daemon=True).start()
        r = _drive_until_recovered(netloc, jpegs, fault_seen, args.slo_s)
        if not killed.is_set():
            raise AssertionError("victim was never killed (probe fired "
                                 "early?)")
        m = scrape_metrics(netloc)
        assert_router_books(m)
        down = m.get("dfd_router_replicas_down_total", 0)
        if down < 1:
            raise AssertionError("router never counted the replica down")
        # relaunch on the SAME port: the scraper must return it to
        # rotation (healthy+ready count back to 2)
        replicas[0] = (_spawn_serve(args, victim_port, ""), victim_port)
        wait_fleet_ready(netloc, 2, timeout=args.ready_timeout_s)
        _log("relaunched replica rejoined the rotation")
        # one more loaded pass over the healed fleet, books still exact
        stop = threading.Event()
        posters = [_Poster(netloc, jpegs, stop) for _ in range(3)]
        for p in posters:
            p.start()
        time.sleep(2.0)
        stop.set()
        for p in posters:
            p.join(timeout=10)
        ok_after = sum(1 for p in posters for (_, s) in p.samples
                       if s == 200)
        if ok_after == 0:
            raise AssertionError("no 200s after the replica rejoined")
        m = scrape_metrics(netloc)
        assert_router_books(m)
        return {"scenario": "replica_kill",
                "recovery_s": r["recovery_s"],
                "statuses": r["statuses"],
                "replicas_down": down,
                "books": {k: v for k, v in m.items()
                          if k.startswith("dfd_router_") and
                          k.endswith("_total")}}
    finally:
        _terminate(router_proc)
        for proc, _ in replicas:
            _terminate(proc)


def _stream_replica_cmd(args, port: int, state_dir: str,
                        event_dir: str) -> list:
    # the stream_resume topology, one replica's worth (shared event dir:
    # a migrated session appends to the SAME per-stream JSONL, so the
    # coherence check covers the migration seam exactly like the
    # restart seam)
    return _stream_cmd(args, port, state_dir, event_dir)


def run_replica_migrate(args) -> dict:
    """Live migration: drive a stream through the router onto its home
    replica, drain that replica (sessions snapshot + restore onto the
    peer via the PR 10 state machinery), finish the stream through the
    router, and require the final status to be BIT-IDENTICAL to an
    undrained replay — plus exact router books and a connected
    per-stream event log across the migration seam."""
    import numpy as np
    rng = np.random.default_rng(11)
    s = args.image_size
    frames = [rng.integers(0, 255, (s, s, 3), dtype=np.uint8)
              for _ in range(20)]
    phase1, phase2 = frames[:8], frames[8:]
    n1, n_total = len(phase1) // 2, len(frames) // 2
    sid = "migrate-me"

    def run_topology(drain: bool) -> Tuple[dict, str, dict]:
        event_dir = tempfile.mkdtemp(prefix="chaos-fleet-events-")
        replicas = []
        router_proc = None
        try:
            for _ in range(2):
                port = free_port()
                state_dir = tempfile.mkdtemp(prefix="chaos-fleet-state-")
                proc = subprocess.Popen(
                    _stream_replica_cmd(args, port, state_dir, event_dir),
                    cwd=_REPO, env=_child_env(),
                    stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
                replicas.append((proc, port))
            for _, port in replicas:
                wait_ready(f"127.0.0.1:{port}",
                           timeout=args.ready_timeout_s)
            router_proc, netloc = spawn_router(
                [f"127.0.0.1:{port}" for _, port in replicas],
                data_plane=args.data_plane)
            wait_fleet_ready(netloc, 2, timeout=args.ready_timeout_s)
            rport = int(netloc.split(":")[1])
            client = _StreamClient(rport)
            client.open(sid)
            client.push_raw(sid, phase1)
            client.wait_scored(sid, n1)      # quiesce before any drain
            # who holds the session? ask the replicas directly
            owner = None
            for _, port in replicas:
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=10)
                conn.request("GET", "/streams")
                listing = json.loads(conn.getresponse().read())
                conn.close()
                if sid in listing.get("streams", []):
                    owner = port
            if owner is None:
                raise AssertionError(f"no replica holds stream {sid!r}")
            if drain:
                _log(f"draining replica 127.0.0.1:{owner} (owns {sid})")
                conn = http.client.HTTPConnection("127.0.0.1", rport,
                                                  timeout=60)
                conn.request("POST", f"/replicas/127.0.0.1:{owner}/drain")
                resp = conn.getresponse()
                report = json.loads(resp.read())
                conn.close()
                if resp.status != 200 or report.get("failed") or \
                        sid not in report.get("migrated", []):
                    raise AssertionError(f"drain did not migrate {sid}: "
                                         f"{report}")
                m = scrape_metrics(netloc)
                if m.get("dfd_router_streams_migrated_total", 0) != 1:
                    raise AssertionError("streams_migrated_total != 1")
                if m.get("dfd_router_migration_aborts_total", 0):
                    raise AssertionError("migration aborted")
                # the session must now live on the OTHER replica
                other = next(p for _, p in replicas if p != owner)
                conn = http.client.HTTPConnection("127.0.0.1", other,
                                                  timeout=10)
                conn.request("GET", "/streams")
                listing = json.loads(conn.getresponse().read())
                conn.close()
                if sid not in listing.get("streams", []):
                    raise AssertionError("migrated session not on the "
                                         "target replica")
            client.push_raw(sid, phase2)     # routed via the override
            final = client.wait_scored(sid, n_total)
            m = scrape_metrics(netloc)
            assert_router_books(m)
            if drain and m.get("dfd_router_migrated_total", 0) < 1:
                raise AssertionError("no request resolved via the "
                                     "migration override")
            return final, event_dir, m
        finally:
            if router_proc is not None:
                _terminate(router_proc)
            for proc, _ in replicas:
                _terminate(proc)

    final_migrated, event_dir, m = run_topology(drain=True)
    final_replay, _, _ = run_topology(drain=False)
    got, want = _comparable(final_migrated), _comparable(final_replay)
    if got != want:
        raise AssertionError(
            "migrated stream diverged from the undrained replay:\n"
            f"migrated: {json.dumps(got, sort_keys=True)}\n"
            f"replay:   {json.dumps(want, sort_keys=True)}")
    _log(f"migrated stream bit-identical to undrained replay (verdict "
         f"{got['verdict']!r}, {got['counters']['windows_scored']} "
         f"windows)")
    # per-stream event log: ONE coherent connected stream across the
    # migration seam (both replicas appended to the same JSONL)
    log_path = os.path.join(event_dir, f"{sid}.events.jsonl")
    with open(log_path) as f:
        events = [json.loads(line) for line in f]
    by_machine: Dict[tuple, list] = {}
    for ev in events:
        by_machine.setdefault(
            (ev.get("scope"), ev.get("track_id")), []).append(ev)
    for key, evs in by_machine.items():
        if not all(a["to"] == b["from"] for a, b in zip(evs, evs[1:])):
            raise AssertionError(f"event log transition path for {key} "
                                 f"broken across the migration: {evs}")
    _log(f"event log coherent across the migration ({len(events)} "
         f"transition(s))")
    return {"scenario": "replica_migrate",
            "windows_scored": got["counters"]["windows_scored"],
            "verdict": got["verdict"],
            "migrated": m.get("dfd_router_streams_migrated_total", 0),
            "events": len(events)}


# ---------------------------------------------------------------------------
# fleet_elastic (ISSUE 18): autoscaler + backfill tenant through a spike,
# a replica SIGKILL and a scale-in — exact books on BOTH tenants
# ---------------------------------------------------------------------------

def _await(probe, what: str, timeout_s: float,
           poll_s: float = 0.2) -> float:
    """Poll ``probe()`` until true; returns seconds waited."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        try:
            if probe():
                return time.monotonic() - t0
        except OSError:
            pass
        time.sleep(poll_s)
    raise AssertionError(f"{what} not observed within {timeout_s:.0f}s")


def _router_json(netloc: str, path: str) -> dict:
    host, port = netloc.split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=10)
    try:
        conn.request("GET", path)
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


def _find_pid_by_cmdline(*needles: str) -> Optional[int]:
    """Linux /proc scan: the pid whose cmdline contains every needle
    (the autoscaler's children are the ROUTER's subprocesses, so the
    harness has no Popen handle to SIGKILL — the pid is the handle)."""
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                joined = f.read().decode(errors="replace").replace(
                    "\0", " ")
        except OSError:
            continue
        if all(n in joined for n in needles):
            return int(pid)
    return None


def _write_backfill_corpus(work: str, image_size: int,
                           fake: int = 7, real: int = 6,
                           frames: int = 2) -> Tuple[str, str, dict]:
    """A small packed corpus + manifest for the tenant (the
    tests/test_backfill.py idiom): returns (pack, manifest_path,
    manifest).  All imports here are jax-free (DFD001)."""
    import numpy as np
    from PIL import Image

    from deepfake_detection_tpu.backfill.manifest import (
        build_manifest_from_pack, save_manifest)
    from deepfake_detection_tpu.data.packed import write_pack

    root = os.path.join(work, "corpus")
    rng = np.random.default_rng(0)
    for kind, n in (("fake", fake), ("real", real)):
        for c in range(n):
            d = os.path.join(root, kind, f"c{c}")
            os.makedirs(d, exist_ok=True)
            for i in range(frames):
                Image.fromarray(rng.integers(
                    0, 255, (image_size, image_size, 3),
                    dtype=np.uint8)).save(
                    os.path.join(d, f"{i}.jpg"), quality=92)
        with open(os.path.join(root, f"{kind}_list.txt"), "w") as f:
            f.write("".join(f"c{c}:{frames}\n" for c in range(n)))
    pack = os.path.join(work, "pack")
    write_pack(root, pack, image_size=0, frames_per_clip=frames,
               shard_size=8, workers=2)
    manifest = build_manifest_from_pack(pack, shard_clips=4)
    mpath = os.path.join(work, "manifest.json")
    save_manifest(mpath, manifest)
    return pack, mpath, manifest


def run_fleet_elastic(args) -> dict:
    """ISSUE 18: the self-operating fleet through every transition at
    once.  One cold replica + the SLO autoscaler (max 2) + the backfill
    tenant on the idle slot; then, under live traffic:

    * a closed-loop spike breaches the depth line → the tenant YIELDS
      its worker (SIGTERM → exit-75 lease release) and the autoscaler
      spawns into the freed slot;
    * the NEW (still warming) replica is SIGKILLed → the control loop
      books it killed and respawns under the persisting breach;
    * load drops → drain-first scale-in back to 1 replica, the tenant
      relaunches onto the re-idled slot and runs the corpus dry.

    Asserts: exact router books AND exact backfill books (manifest
    clips == scored + failed + skipped_dup), zero client-visible
    failures, zero post-transition recompiles on surviving replicas,
    replica books (spawned == retired + killed + alive) and a bit-exact
    replay of the recorded decision trace."""
    jpegs = make_jpegs(8, args.src_size)
    work = tempfile.mkdtemp(prefix="chaos-elastic-")
    # the tenant scores the PAPER flagship at 160² (~0.8 clips/s on this
    # class of box): the corpus must outlive replica warmup + the spike
    # gate, or the worker runs it dry before there is anything to yield
    pack, mpath, manifest = _write_backfill_corpus(
        work, 160, fake=15, real=15, frames=2)
    out = os.path.join(work, "run")
    trace = os.path.join(work, "autoscale.jsonl")
    port = free_port()
    netloc = f"127.0.0.1:{port}"
    replica_args = (f"--model {args.model} --image-size "
                    f"{args.image_size} --img-num 1 --buckets 1,4 "
                    f"--batch-deadline-ms 5 --max-queue 64")
    backfill_args = (f"--data-packed {pack} "
                     f"--model efficientnet_deepfake_v4 "
                     f"--batch-size 2 --workers 1 --lease-ttl-s 60")
    cmd = [sys.executable, "-m", "deepfake_detection_tpu.runners.router",
           "--port", str(port),
           "--spawn", "1", "--replica-args", replica_args,
           "--data-plane", args.data_plane,
           "--scrape-interval-s", "0.2", "--health-fail-after", "2",
           "--autoscale", "--min-replicas", "1", "--max-replicas", "2",
           "--autoscale-interval-s", "0.5",
           "--slo-p99-ms", "100000",          # breach via depth only:
           # a wall-clock p99 line is nondeterministic on a shared box
           "--autoscale-depth-high", "2", "--autoscale-depth-low", "1",
           "--autoscale-up-samples", "2", "--autoscale-down-samples", "6",
           "--autoscale-up-cooldown-s", "3",
           "--autoscale-down-cooldown-s", "5",
           "--autoscale-trace", trace,
           "--backfill-tenant", mpath, "--backfill-out", out,
           "--backfill-max-workers", "1",
           "--backfill-yield-timeout-s", "60",
           "--backfill-args", backfill_args]
    _log("spawn elastic router: " + " ".join(cmd))
    router_proc = subprocess.Popen(cmd, cwd=_REPO, env=_child_env(),
                                   stdout=subprocess.DEVNULL,
                                   stderr=subprocess.DEVNULL)
    stop = threading.Event()
    posters: List[_Poster] = []
    try:
        wait_fleet_ready(netloc, 1, timeout=args.ready_timeout_s)
        # the tenant must be ON the idle slot and its worker past
        # startup before the spike: a shard lease in <out>/leases/
        # proves the worker's SIGTERM→75 handler is installed (the
        # runner arms it in main(), before any shard is leased)
        lease_dir = os.path.join(out, "leases")
        _await(lambda: scrape_metrics(netloc).get(
                   "dfd_router_backfill_workers", 0) >= 1,
               "backfill tenant worker on the idle slot", 120.0)
        _await(lambda: os.path.isdir(lease_dir) and
                   any(f.endswith(".lease")
                       for f in os.listdir(lease_dir)),
               "tenant worker's first shard lease", 300.0)
        baseline_ids = set(_router_json(netloc, "/replicas"))
        _log(f"tenant worker leased a shard; spiking over "
             f"{sorted(baseline_ids)}")

        posters = [_Poster(netloc, jpegs, stop) for _ in range(6)]
        for p in posters:
            p.start()
        # spike → tenant yield (exit-75) → spawn into the freed slot
        t_yield = _await(lambda: scrape_metrics(netloc).get(
                             "dfd_router_backfill_yields_total", 0) >= 1,
                         "backfill yield at the spike", 120.0)
        _await(lambda: scrape_metrics(netloc).get(
                   "dfd_router_replicas_spawned_total", 0) >= 2,
               "scale-up spawn after the yield", 120.0)
        _log(f"tenant yielded {t_yield:.1f}s into the spike; "
             f"scale-up spawned")

        # SIGKILL the NEW replica while it warms: the harness holds no
        # Popen for it (it is the router's child), so find it via /proc
        def new_replica() -> Optional[str]:
            fresh = set(_router_json(netloc, "/replicas")) - baseline_ids
            return sorted(fresh)[0] if fresh else None

        _await(lambda: new_replica() is not None,
               "the new replica registering", 60.0)
        victim_id = new_replica()
        victim_port = victim_id.split(":")[1]
        # the trailing space rides on argv's NUL terminator: it stops
        # "--port 5872" from matching a port that merely extends it
        victim_pid = _find_pid_by_cmdline(
            "deepfake_detection_tpu.runners.serve",
            f"--port {victim_port} ")
        if victim_pid is None:
            raise AssertionError(
                f"no serve process found for {victim_id}")
        _log(f"SIGKILL warming replica {victim_id} (pid {victim_pid})")
        os.kill(victim_pid, signal.SIGKILL)
        _await(lambda: scrape_metrics(netloc).get(
                   "dfd_router_replicas_killed_total", 0) >= 1,
               "the kill being booked", 60.0)
        # the breach persists under the posters: the loop must respawn
        # and warm a replacement INTO the live spike
        wait_fleet_ready(netloc, 2, timeout=args.ready_timeout_s)
        _log("replacement replica warmed under load (2 ready)")
        time.sleep(2.0)          # loaded pass over the grown fleet
        compiles0 = labeled_family(
            scrape_metrics_labeled(netloc),
            "dfd_serving_backend_compiles_total")

        stop.set()
        for p in posters:
            p.join(timeout=30)
        # idle → drain-first scale-in back to the floor
        _await(lambda: scrape_metrics(netloc).get(
                   "dfd_router_replicas_retired_total", 0) >= 1,
               "drain-first retirement after load off", 120.0)
        wait_fleet_ready(netloc, 1, timeout=60.0)
        compiles1 = labeled_family(
            scrape_metrics_labeled(netloc),
            "dfd_serving_backend_compiles_total")
        for labels, c1 in compiles1.items():
            c0 = compiles0.get(labels)
            if c0 is not None and c1 != c0:
                raise AssertionError(
                    f"surviving replica recompiled through the "
                    f"transitions: {labels} {c0:.0f} -> {c1:.0f}")
        _log(f"zero post-transition recompiles on "
             f"{len(compiles1)} surviving replica(s)")

        # the tenant takes the re-idled slot back and runs the corpus
        # dry (shard leases + done markers make every yield resumable)
        _await(lambda: (_router_json(netloc, "/autoscaler")
                        .get("tenant") or {}).get("corpus_done", False),
               "the tenant finishing the corpus", 600.0, poll_s=1.0)
        _log("backfill corpus complete")

        m = scrape_metrics(netloc)
        assert_router_books(m)
        spawned = m.get("dfd_router_replicas_spawned_total", 0)
        retired = m.get("dfd_router_replicas_retired_total", 0)
        killed = m.get("dfd_router_replicas_killed_total", 0)
        alive = m.get("dfd_router_ready_replicas", 0) + \
            m.get("dfd_router_warming_replicas", 0)
        if spawned != retired + killed + alive:
            raise AssertionError(
                f"replica books do not balance: spawned {spawned:.0f} "
                f"!= retired {retired:.0f} + killed {killed:.0f} + "
                f"alive {alive:.0f}")
        statuses: Dict[int, int] = {}
        for p in posters:
            for _, s in p.samples:
                statuses[s] = statuses.get(s, 0) + 1
        bad = {s: c for s, c in statuses.items()
               if s not in (200, 429, 503)}
        if bad:
            raise AssertionError(
                f"client-visible failures through the transitions: "
                f"{bad} (statuses {statuses})")
        yields = m.get("dfd_router_backfill_yields_total", 0)
        _log(f"replica books balance ({spawned:.0f} == {retired:.0f} + "
             f"{killed:.0f} + {alive:.0f}); statuses {statuses}")
    finally:
        stop.set()
        _terminate(router_proc, timeout=60.0)

    # both tenants' books, audited AFTER the graceful shutdown:
    # the backfill identity is read from the run dir itself
    from deepfake_detection_tpu.backfill.writer import collect_books
    books = collect_books(out, manifest)
    if not books["balanced"]:
        raise AssertionError(f"backfill books do not balance: {books}")
    if books["scored"] + books["failed"] + books["skipped_dup"] != \
            books["manifest_clips"]:
        raise AssertionError(f"backfill identity broken: {books}")
    _log(f"backfill books balance: {books['manifest_clips']} manifest "
         f"clips == {books['scored']} scored + {books['failed']} "
         f"failed + {books['skipped_dup']} skipped_dup")
    from deepfake_detection_tpu.fleet.autoscaler import replay_trace
    rep = replay_trace(trace)
    if not rep["match"]:
        raise AssertionError(
            f"decision-trace replay diverged: {rep['mismatches'][:3]}")
    _log(f"decision trace replays bit-exactly ({rep['n']} ticks)")
    return {"scenario": "fleet_elastic",
            "yield_s": t_yield,
            "statuses": statuses,
            "replica_books": {"spawned": spawned, "retired": retired,
                              "killed": killed, "alive": alive},
            "backfill_books": {k: books[k] for k in
                               ("manifest_clips", "scored", "failed",
                                "skipped_dup")},
            "trace_ticks": rep["n"]}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scenario", default="all",
                    help=f"comma list of {SCENARIOS} or 'all'")
    ap.add_argument("--model", default="mobilenetv3_small_100",
                    help="registered model (default sized for CPU boxes)")
    ap.add_argument("--models", default="",
                    help="extra model-table specs (ServeConfig --models "
                         "grammar): serve scenarios then run with N "
                         "models loaded — the ISSUE 14 invariant drive")
    ap.add_argument("--cascade", default="",
                    help="with --models: route un-addressed load "
                         "student-first through this --models id "
                         "(band [0,1], every clip escalates — both "
                         "tiers see every fault)")
    ap.add_argument("--image-size", type=int, default=32)
    ap.add_argument("--src-size", type=int, default=64)
    ap.add_argument("--slo-s", type=float, default=15.0,
                    help="max seconds from fault to next 200")
    ap.add_argument("--watchdog-timeout-s", type=float, default=2.0)
    ap.add_argument("--breaker-threshold", type=int, default=5)
    ap.add_argument("--cache-entries", type=int, default=0,
                    help="run the serve scenarios with the verdict "
                         "cache enabled at this capacity (ISSUE 17): "
                         "the books identity is then asserted with a "
                         "live cache_hit term through every fault")
    ap.add_argument("--ready-timeout-s", type=float, default=900.0)
    ap.add_argument("--data-plane", default="evloop",
                    choices=["evloop", "threads"],
                    help="router data plane for the fleet scenarios "
                         "(ISSUE 16: chaos must hold on both)")
    ap.add_argument("--out", default="", help="write a JSON report here")
    args = ap.parse_args(argv)

    names = list(SCENARIOS) if args.scenario == "all" else \
        [s.strip() for s in args.scenario.split(",") if s.strip()]
    for n in names:
        if n not in SCENARIOS:
            ap.error(f"unknown scenario {n!r} (known: {SCENARIOS})")

    results, failures = [], []
    for n in names:
        _log(f"=== scenario {n} ===")
        try:
            if n == "torn_reload":
                results.append(run_torn_reload(args))
            elif n == "stream_resume":
                results.append(run_stream_resume(args))
            elif n == "replica_kill":
                results.append(run_replica_kill(args))
            elif n == "replica_migrate":
                results.append(run_replica_migrate(args))
            elif n == "fleet_elastic":
                results.append(run_fleet_elastic(args))
            else:
                results.append(run_serve_fault(args, n))
            _log(f"=== {n} PASS ===")
        except (AssertionError, TimeoutError, OSError) as e:
            _log(f"=== {n} FAIL: {e} ===")
            failures.append((n, str(e)))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"results": results,
                       "failures": failures}, f, indent=2)
        _log(f"wrote {args.out}")
    if failures:
        _log(f"{len(failures)}/{len(names)} scenario(s) FAILED")
        return 1
    _log(f"all {len(names)} scenario(s) passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
