"""Training resilience layer: preemption-safe stop, NaN/spike guard, stall
watchdog.

The reference stack dies wholesale on any fault: an ``mp.spawn`` worker
fault kills the job, a preempted host restarts from the last *epoch*
boundary, a NaN step silently poisons the params, and a hung collective
hangs forever.  This module gives the runner four coordinated defenses:

* :class:`PreemptionHandler` — SIGTERM/SIGINT request a stop at the next
  step boundary; the trainer then writes a *synchronous* recovery snapshot
  carrying the exact loop position and the run exits :data:`EXIT_PREEMPTED`
  so a restart wrapper (scripts/train.sh) can relaunch into
  ``--auto-resume``.  A second signal falls through to the original
  handler (an impatient operator can still hard-kill).
* :class:`AnomalyGuard` — host-side policy fed at the trainer's existing
  metric-drain cadence (no extra device syncs): counts non-finite steps,
  flags loss spikes against rolling robust statistics (median/MAD), and
  after K *consecutive* bad steps raises :class:`RewindRequested` so the
  runner restores the last recovery snapshot instead of continuing on
  corrupted state.  The device-side skip (train/steps.py ``nonfinite_guard``)
  keeps params finite in the meantime.
* :class:`StallWatchdog` — a monitor thread fed by step-completion
  heartbeats (the shm ring's worker-heartbeat idiom, one level up).  On
  timeout it dumps every Python thread's stack plus the loop position and
  aborts with :data:`EXIT_WATCHDOG` — turning a silent multi-hour hang
  (stuck collective, wedged loader) into a restartable event.
* :class:`Resilience` — the facade the runner owns: installs/restores the
  signal handlers (context manager, so in-process library use — tests —
  leaves no global state behind), carries the chaos injector, the rewind
  budget, and the watchdog.

Multi-host notes: guard decisions are deterministic functions of the
*replicated* loss/nonfinite scalars, so under normal operation every host
computes the same verdict — but "normal operation" is exactly what a fault
layer must not assume, and the preemption flag is genuinely host-local (each
host gets its own SIGTERM, at its own step boundary).  Multi-process runs
therefore agree on the verdicts IN-BAND: the guard defers its rewind raise
(``coordinated=True``) and :meth:`Resilience.sync_verdicts` max-reduces the
``[stop, rewind]`` flag pair across processes at the trainer's drain cadence
(a deterministic boundary every host reaches, so the collective cannot
one-side).  Any host's verdict wins everywhere, and every host raises
:class:`Preempted` / :class:`RewindRequested` at the SAME boundary — which is
what makes the lockstep recovery snapshot and the collective sharded restore
safe to enter.
"""

from __future__ import annotations

import faulthandler
import itertools
import logging
import os
import signal
import sys
import threading
import time
from collections import deque
from typing import Callable, Optional, Tuple

import numpy as np

from ..chaos import ChaosInjector, chaos_from_env

_logger = logging.getLogger(__name__)

__all__ = ["EXIT_PREEMPTED", "EXIT_WATCHDOG", "Preempted", "RewindRequested",
           "PreemptionHandler", "AnomalyGuard", "StallWatchdog", "Resilience",
           "allreduce_flags"]

#: exit code after a signal-requested stop with a recovery snapshot on disk
#: (os.EX_TEMPFAIL: "try again later" — the restart wrapper relaunches)
EXIT_PREEMPTED = 75
#: exit code of a stall-watchdog abort (distinct from EXIT_PREEMPTED so the
#: wrapper can count the two failure classes separately if it wants to)
EXIT_WATCHDOG = 85


class Preempted(Exception):
    """Raised by the trainer at a step boundary after a stop request; the
    recovery snapshot is already on disk when this propagates."""

    def __init__(self, epoch: int, batch_idx: int, signum: int):
        super().__init__(
            f"preempted by signal {signum} at epoch {epoch} "
            f"batch {batch_idx}; recovery snapshot written")
        self.epoch = epoch
        self.batch_idx = batch_idx
        self.signum = signum


class RewindRequested(Exception):
    """Raised by the guard when training should rewind to the last
    recovery snapshot instead of continuing on suspect state."""


#: lockstep round counter for :func:`allreduce_flags` key namespacing —
#: advances identically on every host because the trainer only syncs at
#: deterministic loop boundaries
_sync_round = itertools.count()
#: how long one host waits for a peer's verdict before declaring the job
#: wedged; generous — peers reach the same LOOP boundary at skewed wall
#: times (compile variance, straggler steps)
SYNC_TIMEOUT_MS = int(os.environ.get("DFD_VERDICT_SYNC_TIMEOUT_MS",
                                     str(10 * 60 * 1000)))


def allreduce_flags(flags: np.ndarray) -> np.ndarray:
    """Max-reduce a small int32 flag vector across all jax processes.

    The in-band agreement primitive for the host-local verdict scalars
    (preemption stop, guard rewind): any host's 1 becomes every host's 1.
    Runs over the jax.distributed coordination-service KV store — a few
    bytes of gRPC, no XLA computation — so it works on every backend
    (CPU cross-process XLA computations are unimplemented in some jaxlib
    builds) and never competes with the step for device time.

    COLLECTIVE in cadence: every process must call it the same number of
    times, at the same boundary; the trainer guarantees that by syncing
    only at the metric-drain cadence (``last_batch or batch_idx %
    log_interval == 0``), a pure function of loop indices every host walks
    identically.  Single-process runs return the input unchanged without
    touching the runtime.
    """
    import jax                          # lazy: keep this module jax-light
    flags = np.asarray(flags, np.int32)
    if jax.process_count() == 1:
        return flags
    from jax._src import distributed  # no public KV-store accessor
    client = distributed.global_state.client
    if client is None:  # pragma: no cover - pod runtimes init elsewhere
        raise RuntimeError(
            "multi-process run without a jax.distributed coordination "
            "client: verdict agreement needs the KV store")
    rnd = next(_sync_round)
    me = jax.process_index()
    client.key_value_set(f"dfd/verdict/{rnd}/{me}",
                         ",".join(str(int(v)) for v in flags))
    out = flags.copy()
    for r in range(jax.process_count()):
        if r == me:
            continue
        peer = client.blocking_key_value_get(f"dfd/verdict/{rnd}/{r}",
                                             SYNC_TIMEOUT_MS)
        out = np.maximum(out, np.fromiter(
            (int(v) for v in peer.split(",")), np.int32, len(flags)))
    # a long run syncs every drain boundary — drop a FINISHED round's keys
    # or the coordination service leaks a key per process per round.  The
    # previous round is complete by construction (every peer answered it
    # before writing this one); deleting our own rnd key would race a slow
    # peer's pending get.
    delete = getattr(client, "key_value_delete", None)
    if rnd > 0 and delete is not None:
        delete(f"dfd/verdict/{rnd - 1}/{me}")
    return out


class PreemptionHandler:
    """First SIGTERM/SIGINT sets a flag checked at step boundaries; a
    second delivery restores the original disposition and re-raises, so a
    stuck run can still be killed the ordinary way."""

    def __init__(self, signals=(signal.SIGTERM, signal.SIGINT)):
        self._signals = tuple(signals)
        self._previous: dict = {}
        self.stop_requested = False
        self.signum: Optional[int] = None

    def install(self) -> bool:
        """Install handlers; False when not possible (non-main thread)."""
        try:
            for s in self._signals:
                self._previous[s] = signal.signal(s, self._handle)
        except ValueError:          # signal only works in the main thread
            self.uninstall()
            return False
        return True

    def uninstall(self) -> None:
        for s, prev in list(self._previous.items()):
            try:
                signal.signal(s, prev)
            except (ValueError, TypeError):
                pass
        self._previous.clear()

    def _handle(self, signum, frame) -> None:
        if self.stop_requested:
            # second signal: hand control back to the original handler
            # (default SIGTERM kills; SIGINT raises KeyboardInterrupt)
            self.uninstall()
            signal.raise_signal(signum)
            return
        self.stop_requested = True
        self.signum = signum
        _logger.warning(
            "signal %d received: stopping at the next step boundary "
            "(second signal force-kills)", signum)


class AnomalyGuard:
    """Host-side anomaly policy over per-step loss scalars.

    Fed from the trainer's metric drain (the only place the host reads
    device scalars anyway).  Three signals combine into one "bad step"
    verdict:

    * the device-side non-finite flag (loss or global grad-norm),
    * a non-finite loss read on host (covers guard-off steps), and
    * a loss spike: ``|loss - median| > zmax * 1.4826 * MAD`` over the last
      ``spike_window`` *accepted* losses (robust statistics — a previous
      spike does not drag the baseline; MAD is floored so a flat early
      window cannot divide by ~0).

    ``rewind_after`` consecutive bad steps raise :class:`RewindRequested`.
    Isolated bad steps only count (the device-side skip already protected
    the params); the streak resets on any good step and on rewind.
    """

    def __init__(self, spike_window: int = 0, spike_zmax: float = 8.0,
                 rewind_after: int = 3, coordinated: bool = False):
        self.spike_window = int(spike_window)
        self.spike_zmax = float(spike_zmax)
        self.rewind_after = max(1, int(rewind_after))
        # multi-process: defer the rewind raise — the verdict scalar is
        # max-reduced across hosts (Resilience.sync_verdicts) so every host
        # raises at the same boundary, or none does
        self.coordinated = bool(coordinated)
        self.rewind_wanted = False
        self.rewind_reason = ""
        self._hist: deque = deque(maxlen=max(self.spike_window, 1))
        self.bad_streak = 0
        self.nonfinite_total = 0
        self.spike_total = 0

    def is_spike(self, loss: float) -> bool:
        if self.spike_window <= 0 or len(self._hist) < self.spike_window:
            return False
        med = float(np.median(self._hist))
        mad = float(np.median(np.abs(np.asarray(self._hist) - med)))
        scale = max(1.4826 * mad, 1e-3 * max(abs(med), 1.0))
        return abs(loss - med) > self.spike_zmax * scale

    def observe(self, step_index: int, loss: float,
                nonfinite: bool) -> bool:
        """Record one step; returns True when the step was bad.  Raises
        :class:`RewindRequested` on the ``rewind_after``-th consecutive
        bad step."""
        bad = bool(nonfinite) or not np.isfinite(loss)
        if bad:
            self.nonfinite_total += 1
        elif self.is_spike(loss):
            bad = True
            self.spike_total += 1
            _logger.warning(
                "loss spike at update %d: %.5f vs rolling median %.5f",
                step_index, loss, float(np.median(self._hist)))
        else:
            self._hist.append(float(loss))
        if not bad:
            self.bad_streak = 0
            return False
        self.bad_streak += 1
        if self.bad_streak >= self.rewind_after:
            reason = (f"{self.bad_streak} consecutive bad steps "
                      f"(last at update {step_index}, loss {loss!r})")
            if not self.coordinated:
                raise RewindRequested(reason)
            # multi-process: record the verdict; sync_verdicts raises it on
            # EVERY host at the next drain boundary
            self.rewind_wanted = True
            self.rewind_reason = reason
        return True

    def reset_streak(self) -> None:
        self.bad_streak = 0
        self.rewind_wanted = False
        self.rewind_reason = ""


class StallWatchdog:
    """Monitor thread fed by step-completion heartbeats.

    ``timeout`` seconds without a :meth:`beat` → dump all Python thread
    stacks + the loop position to stderr and abort the process with
    :data:`EXIT_WATCHDOG`.  ``os._exit`` semantics (via the injectable
    ``exit_fn``) are deliberate: a wedged collective or a deadlocked
    loader thread would block any graceful teardown path.

    The window before the FIRST beat is ``first_grace`` × longer: the
    first train step XLA-compiles (minutes at flagship scale) with no
    chance to heartbeat, and a watchdog sized to steady-state step time
    would otherwise abort during compile on every relaunch — a restart
    loop that never completes a step.  Size ``timeout`` itself to cover
    the post-warmup stragglers (a first *eval* compile, a slow epoch
    boundary) — a few multiples of step time is too tight.

    ``dump_path``: the all-thread stack dump also lands in this file
    (``<outdir>/watchdog_dump.txt``) — stderr is routinely lost when the
    restart wrapper relaunches, and a post-mortem needs the stacks.
    Telemetry counters: ``beats_total``; ``near_miss_total`` counts beats
    that arrived with the previous beat older than half the timeout — a
    run skating toward an abort shows up as a rising gauge before it dies.
    """

    def __init__(self, timeout: float,
                 position_fn: Optional[Callable[[], str]] = None,
                 exit_fn: Optional[Callable[[int], None]] = None,
                 first_grace: float = 10.0,
                 dump_path: Optional[str] = None):
        self.timeout = float(timeout)
        self.first_grace = max(1.0, float(first_grace))
        self._position_fn = position_fn or (lambda: "<unknown>")
        self._exit_fn = exit_fn
        self.dump_path = dump_path
        self._last = time.monotonic()
        self._seen_beat = False
        self.beats_total = 0
        self.near_miss_total = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def beat(self) -> None:
        now = time.monotonic()
        if self._seen_beat and now - self._last > 0.5 * self.timeout:
            self.near_miss_total += 1
        self._last = now
        self._seen_beat = True
        self.beats_total += 1

    def beat_age(self) -> float:
        """Seconds since the last heartbeat (telemetry gauge)."""
        return time.monotonic() - self._last

    def start(self) -> None:
        if self.timeout <= 0 or self._thread is not None:
            return
        self._last = time.monotonic()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="dfd-stall-watchdog")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def _run(self) -> None:
        poll = max(0.05, min(self.timeout / 4.0, 5.0))
        while not self._stop.wait(poll):
            idle = time.monotonic() - self._last
            limit = self.timeout if self._seen_beat \
                else self.timeout * self.first_grace
            if idle <= limit:
                continue
            self._fire(idle)
            return

    def _fire(self, idle: float) -> None:
        msg = (f"stall watchdog: no step completed for {idle:.1f}s "
               f"(timeout {self.timeout:.1f}s) at {self._position_fn()}; "
               f"dumping thread stacks and aborting with exit code "
               f"{EXIT_WATCHDOG}")
        _logger.critical(msg)
        try:
            print(msg, file=sys.stderr, flush=True)
            faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
            sys.stderr.flush()
        except Exception:  # noqa: BLE001 — the abort must still happen
            pass
        if self.dump_path:
            # stderr is routinely lost when the restart wrapper relaunches
            # — persist the same dump where --auto-resume will find it
            try:
                with open(self.dump_path, "w") as f:
                    f.write(msg + "\n")
                    faulthandler.dump_traceback(file=f, all_threads=True)
            except Exception:  # noqa: BLE001 — the abort must still happen
                pass
        if self._exit_fn is not None:
            self._exit_fn(EXIT_WATCHDOG)
        else:
            import os
            os._exit(EXIT_WATCHDOG)


class Resilience:
    """Everything the runner threads through the hot loop, in one handle.

    Built by :meth:`from_config`; used as a context manager so signal
    handlers are always restored (the runner is also called in-process by
    tests and by programmatic users).
    """

    def __init__(self, preemption: Optional[PreemptionHandler] = None,
                 guard: Optional[AnomalyGuard] = None,
                 watchdog: Optional[StallWatchdog] = None,
                 chaos: Optional[ChaosInjector] = None,
                 rewind_limit: int = 2):
        self.preemption = preemption
        self.guard = guard
        self.watchdog = watchdog
        self.chaos = chaos if chaos is not None else ChaosInjector("")
        self.rewinds_left = max(0, int(rewind_limit))
        self.position = "<not started>"

    @classmethod
    def from_config(cls, cfg, output_dir: str = "") -> "Resilience":
        import jax                      # lazy: keep this module jax-light
        guard = None
        if cfg.guard_nonfinite != "off" or cfg.guard_spike_window > 0:
            guard = AnomalyGuard(spike_window=cfg.guard_spike_window,
                                 spike_zmax=cfg.guard_spike_zmax,
                                 rewind_after=cfg.guard_rewind_after,
                                 coordinated=jax.process_count() > 1)
        self = cls(preemption=PreemptionHandler(), guard=guard,
                   chaos=chaos_from_env(),
                   rewind_limit=cfg.guard_rewind_limit)
        if cfg.watchdog_timeout > 0:
            dump = os.path.join(output_dir, "watchdog_dump.txt") \
                if output_dir else None
            self.watchdog = StallWatchdog(
                cfg.watchdog_timeout, position_fn=lambda: self.position,
                dump_path=dump)
        return self

    # -- lifecycle -----------------------------------------------------
    def __enter__(self) -> "Resilience":
        if self.preemption is not None and not self.preemption.install():
            _logger.warning("not in the main thread: preemption signal "
                            "handlers not installed")
            self.preemption = None
        if self.watchdog is not None:
            self.watchdog.start()
        return self

    def __exit__(self, *exc) -> None:
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.preemption is not None:
            self.preemption.uninstall()

    # -- hot-loop hooks (all cheap; trainer calls them per step) -------
    @property
    def stop_requested(self) -> bool:
        return self.preemption is not None and self.preemption.stop_requested

    @property
    def stop_signum(self) -> int:
        return self.preemption.signum if self.preemption is not None \
            and self.preemption.signum is not None else signal.SIGTERM

    def heartbeat(self, position: Optional[str] = None) -> None:
        if position is not None:
            self.position = position
        if self.watchdog is not None:
            self.watchdog.beat()

    def note(self, position: str) -> None:
        """Update the reported loop position WITHOUT feeding the watchdog
        a beat — for markers that precede the first completed step (epoch
        start), where a beat would end the watchdog's first-compile grace
        window before the compile it exists to protect."""
        self.position = position

    def observe_step(self, step_index: int, loss: float,
                     nonfinite: bool) -> bool:
        """Guard hook; returns True for a bad step, may raise
        :class:`RewindRequested`."""
        if self.guard is None:
            return bool(nonfinite) or not np.isfinite(loss)
        return self.guard.observe(step_index, loss, nonfinite)

    def sync_verdicts(self) -> Tuple[bool, bool]:
        """Multi-host in-band agreement on the ``[stop, rewind]`` verdicts.

        Max-reduces the host-local preemption flag and the guard's deferred
        rewind verdict across processes and returns the agreed ``(stop,
        rewind)`` pair — any host's verdict wins everywhere.  COLLECTIVE:
        call only at a boundary every process reaches (the trainer's drain
        cadence).  A remote host's stop is adopted locally (so this host
        also exits :data:`EXIT_PREEMPTED` and the restart wrapper relaunches
        the whole job), and an agreed rewind resets every host's streak so
        the replayed span starts clean.
        """
        want_stop = self.stop_requested
        want_rewind = self.guard is not None and self.guard.rewind_wanted
        stop, rewind = (bool(v) for v in
                        allreduce_flags(np.array([want_stop, want_rewind],
                                                 np.int32)))
        if stop and not want_stop:
            # adopt the remote stop so stop_signum/exit-code logic runs
            # exactly as if this host had been signalled itself
            if self.preemption is None:
                self.preemption = PreemptionHandler()   # uninstalled is fine
            self.preemption.stop_requested = True
            _logger.warning("adopting a remote host's preemption stop")
        if rewind and self.guard is not None and not self.guard.rewind_reason:
            self.guard.rewind_reason = "remote host requested rewind"
        return stop, rewind

    def start_rewind(self, reason: str) -> None:
        """Account one rewind; raises when the budget is exhausted."""
        if self.rewinds_left <= 0:
            raise RuntimeError(
                f"rewind budget exhausted ({reason}); aborting rather "
                "than looping on corrupted state")
        self.rewinds_left -= 1
        if self.guard is not None:
            self.guard.reset_streak()
        _logger.warning("rewinding to the last recovery snapshot (%s); "
                        "%d rewind(s) left", reason, self.rewinds_left)
