"""Optional ``jax.profiler`` capture.

Two pieces, both no-ops unless explicitly armed:

* :func:`capture` / :func:`maybe_capture` — a context manager around a
  whole run that starts a ``jax.profiler`` trace into a log directory
  (TensorBoard/XProf-readable). Armed by the bench CLI's ``--profile
  DIR`` flag or the ``DSDDMM_PROFILE=DIR`` env var.
* :func:`capture_window` — a bounded capture (a fraction of a second,
  not a run): start a trace, hold it for ``duration_s``, stop. This is
  the flight recorder's hook — when the watchdog fires an anomaly with
  ``--profile`` armed, a short window catches the device timeline
  *around* the anomaly without paying whole-run capture overhead.
  Refuses (returns False) while another capture is active — two
  concurrent ``jax.profiler`` sessions is an error in jax itself.

The program's names reach a capture through ``obs.trace.span``, which
always enters a ``TraceAnnotation``, whoever started the capture; a
capture changes nothing the program runs.

Everything degrades gracefully: a jax without the profiler API (or a
backend that refuses to start one — :func:`capture_available` probes
without side effects) logs a warning and runs untraced — profiling must
never take down a run.
"""

from __future__ import annotations

import contextlib
import threading
import time  # time.sleep only; clocks go through obs.clock

from distributed_sddmm_tpu.obs import log

_capturing = False


def active() -> bool:
    """True while a capture started here is running (one at a time)."""
    return _capturing


@contextlib.contextmanager
def capture(logdir: str):
    """Run the block under a ``jax.profiler`` trace into ``logdir``."""
    global _capturing
    started = False
    try:
        import jax.profiler

        jax.profiler.start_trace(logdir)
        started = True
        log.info("profiler", "jax.profiler capture started", logdir=logdir)
    except Exception as e:  # noqa: BLE001 — run unprofiled, never die
        log.warn("profiler", "could not start jax.profiler capture",
                 error=f"{type(e).__name__}: {e}")
    _capturing = started
    try:
        yield
    finally:
        _capturing = False
        if started:
            try:
                import jax.profiler

                jax.profiler.stop_trace()
                log.info("profiler", "jax.profiler capture written",
                         logdir=logdir)
            except Exception as e:  # noqa: BLE001
                log.warn("profiler", "jax.profiler stop_trace failed",
                         error=f"{type(e).__name__}: {e}")


def maybe_capture(logdir: str | None = None):
    """``capture(logdir)`` when a directory is given (CLI flag) or set in
    ``DSDDMM_PROFILE``; a null context otherwise."""
    import os

    target = logdir or os.environ.get("DSDDMM_PROFILE")
    if not target:
        return contextlib.nullcontext()
    return capture(target)


def capture_available() -> bool:
    """True when this jax exposes the start/stop trace API (no capture
    is started — a pure probe, safe on any backend)."""
    try:
        import jax.profiler

        return (
            hasattr(jax.profiler, "start_trace")
            and hasattr(jax.profiler, "stop_trace")
        )
    except Exception:  # noqa: BLE001 — absence is a normal answer
        return False


def capture_window(
    logdir: str, duration_s: float = 0.25, block: bool = True,
) -> bool:
    """Capture a short ``jax.profiler`` window into ``logdir``.

    Returns True when a window was attempted (profiler API present and
    no capture already active), False otherwise — the graceful no-op
    contract the flight recorder relies on. ``block=False`` runs the
    window on a daemon thread so an anomaly hook never stalls the
    dispatch path it fired from; the capture that actually lands is
    still best-effort (a backend refusing to start one logs and moves
    on, exactly like :func:`capture`).
    """
    if _capturing or not capture_available():
        return False

    def _window():
        with capture(logdir):
            if active():  # start_trace may still have refused
                time.sleep(duration_s)

    if block:
        _window()
        return True
    threading.Thread(
        target=_window, daemon=True, name="profiler-window"
    ).start()
    return True
