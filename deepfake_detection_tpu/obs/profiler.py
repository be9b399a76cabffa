"""Profiler sessions: the one way to open one, and on-demand capture.

:func:`start_trace` is the only caller of ``jax.profiler.start_trace`` in
the package: the ``--profile N`` window (train/trainer.py), the on-demand
capture below and the serving engine's capture hook all open their session
through it, so every trace an operator takes is taken the same way — with
the Python tracer off.

The ``--profile N`` flag traces the first steps of epoch 0 and is gone —
but "where did this step's milliseconds go" questions arrive mid-run, at
step 300k, on a job nobody wants to restart.  Two triggers start a
bounded trace window on a LIVE run (a trainer, or a server's engine
worker, which counts device batches where the trainer counts updates):

* ``SIGUSR2`` — single-host ergonomics: ``kill -USR2 <pid>``.
* ``touch <output_dir>/PROFILE`` — multi-host ergonomics: the file is
  visible to every rank on a shared filesystem, checked at the trainer's
  drain cadence (one ``stat`` per drain, nothing per step).

Both are **rank-0-gated**: on a shared filesystem, N ranks writing one
trace directory race each other (exactly the hazard the ``--profile``
window's gate documents) — rank 0 traces, the others note the request
and drop it.  Rank 0 also consumes (deletes) the trigger file so one
touch yields one capture, and each capture lands in its own
``profile/ondemand-<update>`` directory so successive captures never
overwrite.

The steady-state cost when idle is two attribute checks per step and one
``stat`` per drain; starting/stopping a window adds the same
``block_until_ready`` + ``stop_trace`` pair the ``--profile`` flag pays.
"""

from __future__ import annotations

import logging
import os
import signal
import threading
from typing import Optional

_logger = logging.getLogger(__name__)

__all__ = ["ProfilerCapture", "TRIGGER_FILENAME", "start_trace"]

TRIGGER_FILENAME = "PROFILE"


def start_trace(trace_dir: str) -> None:
    """Open a profiler session that writes under ``trace_dir``.

    The Python tracer is OFF: it hooks every call of every host thread, and
    the host threads are what feed the chip, so a capture taken with it
    distorts the host-bound loop it looks at.  Host tracer level 1 keeps
    the program's own ``dfd.*`` spans (``TraceAnnotation``) and the
    runtime's launch events, on the clock the device planes use.  The
    device's trace mode is left at the backend's default: a process's
    first capture of a device-bound loop stalls in either mode, and the
    benchmark's ``TRACE_ONLY_XLA`` stalled its second capture too where
    the default did not (PERF.md section 7, PR 34's probe).  Stop with
    ``jax.profiler.stop_trace()``."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


class ProfilerCapture:
    """Bounded on-demand trace windows over a running loop.

    The trainer calls :meth:`poll` at its drain cadence (file trigger
    check) and :meth:`on_step` once per step (window start/stop
    management); the serving engine's worker calls :meth:`on_step` once
    per loop with its device-batch counter and :meth:`poll` while idle.
    ``telemetry`` (optional TrainTelemetry) gets a ``profile_capture``
    event per completed window.
    """

    def __init__(self, output_dir: str, num_steps: int = 20,
                 telemetry=None, signum: int = signal.SIGUSR2):
        self.output_dir = output_dir
        self.num_steps = max(1, int(num_steps))
        self.telemetry = telemetry
        self._signum = signum
        self._prev_handler = None
        self._installed = False
        # _want is written by the signal handler (main thread) and poll();
        # read per step.  bool writes are atomic under the GIL.
        self._want = False
        self.active = False
        self._stop_after = -1
        self._trace_dir = ""
        self.captures_total = 0
        self._lock = threading.Lock()

    # -- triggers ------------------------------------------------------
    def install(self) -> bool:
        """Install the SIGUSR2 handler; False outside the main thread
        (the file trigger still works)."""
        try:
            self._prev_handler = signal.signal(self._signum, self._handle)
        except ValueError:
            return False
        self._installed = True
        return True

    def uninstall(self) -> None:
        if self._installed:
            try:
                signal.signal(self._signum, self._prev_handler
                              or signal.SIG_DFL)
            except (ValueError, TypeError):
                pass
            self._installed = False

    def _handle(self, signum, frame) -> None:
        _logger.warning("signal %d: profiler capture requested "
                        "(next %d steps)", signum, self.num_steps)
        self._want = True

    @property
    def _trigger_path(self) -> str:
        return os.path.join(self.output_dir, TRIGGER_FILENAME)

    def poll(self) -> None:
        """Drain-cadence check of the file trigger (one stat)."""
        if self._want or self.active or not self.output_dir:
            return
        if os.path.exists(self._trigger_path):
            self._want = True
            _logger.warning("%s trigger found: profiler capture requested "
                            "(next %d steps)", self._trigger_path,
                            self.num_steps)

    # -- window management --------------------------------------------
    def on_step(self, step_index: int, sync_ref=None) -> None:
        """Once per train step, after the step dispatch.

        Starts a pending window (the trace then covers the NEXT
        ``num_steps`` dispatches); stops an active one once they have all
        been dispatched (``sync_ref`` — the latest step's loss array — is
        block_until_ready'd first so the trace covers real device
        execution, the --profile window's idiom).
        """
        if self.active and step_index >= self._stop_after:
            self.stop(sync_ref)
        if not self._want or self.active:
            return
        self._want = False
        import jax
        if jax.process_index() != 0:
            # rank-0 gate: trace side effects must not race on a shared
            # filesystem; non-zero ranks drop the request (the trigger
            # file is consumed by rank 0 below)
            return
        self._consume_trigger()
        self._trace_dir = os.path.join(self.output_dir, "profile",
                                       f"ondemand-{step_index}")
        try:
            start_trace(self._trace_dir)
        except Exception as e:          # noqa: BLE001 — never kill the run
            _logger.warning("profiler capture failed to start: %r", e)
            return
        self.active = True
        self._stop_after = step_index + self.num_steps
        _logger.warning("profiler capture started at update %d -> %s "
                        "(%d steps)", step_index, self._trace_dir,
                        self.num_steps)

    def stop(self, sync_ref=None) -> None:
        if not self.active:
            return
        import jax
        try:
            if sync_ref is not None:
                jax.block_until_ready(sync_ref)
            jax.profiler.stop_trace()
        except Exception as e:          # noqa: BLE001
            _logger.warning("profiler capture failed to stop cleanly: %r", e)
        self.active = False
        with self._lock:
            self.captures_total += 1
        _logger.warning("profiler capture written to %s", self._trace_dir)
        if self.telemetry is not None:
            self.telemetry.event("profile_capture", trace_dir=self._trace_dir,
                                 num_steps=self.num_steps)

    def _consume_trigger(self) -> None:
        try:
            os.unlink(self._trigger_path)
        except OSError:
            pass

    def close(self) -> None:
        self.stop()
        self.uninstall()
