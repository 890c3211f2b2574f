"""Stage timing and progress logging (reference: src/logger.{hpp,cpp}).

Same observable behaviour as racon's Logger: ``log()`` (re)starts a stage
timer, ``log(msg)`` prints the elapsed stage seconds to stderr, ``bar``
renders a 20-bin progress bar that overwrites itself, and ``total``
prints the cumulative wall clock.  Two obs-era additions that leave the
stderr format byte-identical:

* **thread safety** — one re-entrant lock serializes ``log``/``bar``/
  ``total``: the r8 streaming pipeline logs from the speculative POA
  consumer and the device watcher threads concurrently with the stage
  thread, which used to interleave (and corrupt) the in-place progress
  bar;
* **obs routing** — every ``log(msg)`` also lands in the trace as an
  instant event and the run total is mirrored into the metrics
  registry, so a Perfetto trace carries the same stage markers the
  reference gets from its stderr log.

One r14 addition: lines emitted under an active job context
(racon_tpu/obs/context.py — i.e. inside a serve worker) get a
``[job 17/tenantA]`` prefix so concurrent jobs' interleaved stderr
is attributable.  The format stays byte-identical when no context is
active (one-shot CLI, library use, tests).

Stage trace spans live at the stage boundaries
(racon_tpu/core/polisher.py, racon_tpu/tpu/polisher.py via
racon_tpu.obs.span), the analog
of the reference's nvprof ranges (src/cuda/cudapolisher.cpp:66-70).
"""

from __future__ import annotations

import sys
import threading
import time


def _ctx_prefix() -> str:
    """``"[job 17/tenantA] "`` under an active job context, else
    ``""`` — never raises (logging must never take the polish
    down)."""
    try:
        from racon_tpu.obs import context as obs_context
        ctx = obs_context.current()
    except Exception:
        return ""
    if ctx is None:
        return ""
    return f"[job {ctx.job_id}/{ctx.tenant}] "


class Logger:
    def __init__(self):
        self._time = 0.0
        self._start = time.monotonic()
        self._bar_state = 0
        self._lock = threading.RLock()

    def _trace(self, message: str) -> None:
        try:
            from racon_tpu.obs.trace import TRACER
            TRACER.add_instant(message, cat="log")
        except Exception:
            pass   # logging must never take the polish down

    def log(self, message: str | None = None) -> None:
        with self._lock:
            now = time.monotonic()
            if message is None:
                self._start = now
                return
            elapsed = now - self._start
            self._time += elapsed
            print(f"{_ctx_prefix()}{message} {elapsed:.6f} s",
                  file=sys.stderr)
            self._start = now
        self._trace(message)

    def bar(self, message: str) -> None:
        with self._lock:
            self._bar_state += 1
            percent = self._bar_state * 5
            bar = "=" * self._bar_state + ">" + " " * (20 - self._bar_state)
            end = "\n" if self._bar_state == 20 else ""
            # \r redraw only makes sense on a terminal; piped stderr
            # (daemon logs, bench captures) gets ONE final line per
            # bar in the same format instead of 20 \r frames
            try:
                tty = sys.stderr.isatty()
            except (AttributeError, ValueError):
                tty = False
            if tty or self._bar_state == 20:
                lead = "\r" if tty else ""
                print(f"{lead}{_ctx_prefix()}{message} [{bar}] "
                      f"{percent}%", end=end,
                      file=sys.stderr, flush=True)
            if self._bar_state == 20:
                now = time.monotonic()
                self._time += now - self._start
                self._start = now
                self._bar_state = 0

    def total(self, message: str) -> None:
        with self._lock:
            self._time += time.monotonic() - self._start
            total = self._time
            print(f"{_ctx_prefix()}{message} {total:.6f} s",
                  file=sys.stderr)
        try:
            from racon_tpu.obs.metrics import REGISTRY
            REGISTRY.set("logger_total_s", round(total, 6))
        except Exception:
            pass
        self._trace(message)
