"""Thread-safe span tracer emitting Chrome trace-event JSON.

Spans are recorded as complete ("ph": "X") events with microsecond
timestamps relative to a process-wide monotonic epoch, attributed to
the recording thread (Perfetto nests same-thread spans by ts/dur, so
``with span(...)`` nesting renders as a flame graph per thread).
Watcher threads record device dispatch intervals onto named virtual
lanes (``lane="device"``), keeping per-dispatch device time visually
separate from host work.

:class:`span` is the one span call: the same block also lands in a
concurrent JAX profile as a ``TraceAnnotation`` on the profiler's
clock, so device idle gaps in a profile are named by the program's
own spans.  Chrome-JSON recording is off by default; it is enabled by
:func:`enable_trace` (the CLI's ``--trace PATH``) or by setting
``RACON_TPU_TRACE=PATH`` in the environment (library runs, tests).
The recorded buffer is written by :func:`write_trace` — recording
never touches the filesystem on the hot path.

Request-scoped additions (r14): every event recorded under an active
job context (racon_tpu/obs/context.py) is auto-tagged with
``{"job", "tenant", "trace_id"}`` in its ``args``, and the serve
daemon turns on :meth:`Tracer.enable_job_capture` — a bounded
per-job span index (an LRU of small deques, NOT the unbounded full
buffer) that backs ``submit --trace`` and the ``inspect``
subcommand without the daemon accumulating an ever-growing trace.
Flow events (``ph: s/t/f``) tie a tenant's unit-submit span to the
shared fused-dispatch device span so Perfetto answers "whose work
rode this megabatch" (racon_tpu/tpu/executor.py).

Determinism: timestamps feed only the emitted JSON, never control
flow.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import OrderedDict, deque

#: the one sanctioned monotonic clock for racon_tpu timing (see the
#: obs lint); trace timestamps are offsets from _EPOCH in microseconds
now = time.monotonic

#: the one sanctioned WALL clock — for forensic stamps that must stay
#: comparable across process incarnations (the serve journal's record
#: timestamps); measurements still go through now()/span()
wall_now = time.time

_EPOCH = time.monotonic()


def _us(t: float) -> float:
    return (t - _EPOCH) * 1e6


# context.py is stdlib-only, so this import cannot cycle back here
from racon_tpu.obs.context import tag_args as _tag_args  # noqa: E402


def epoch_offset(t: float) -> float:
    """Seconds since the trace epoch — the shared timebase for trace
    ``ts`` values and flight-recorder event timestamps, so ``inspect``
    can interleave the two without clock reconciliation."""
    return t - _EPOCH


def epoch_wall() -> float:
    """Wall-clock time of this process's trace epoch — the anchor a
    fleet assembler (racon_tpu/obs/assemble.py) uses to lift this
    process's monotonic epoch offsets onto the wall clock:
    ``wall_t ≈ epoch_wall() + epoch_offset(t)``.  Forensics only;
    never feeds control flow or bytes."""
    return wall_now() - (now() - _EPOCH)


class Tracer:
    # virtual lanes get tids above this floor so they sort after the
    # real threads in the Perfetto track list
    _LANE_TID0 = 1 << 20

    # bounded per-job index: spans kept per job, jobs kept total
    # (oldest job evicted) — sized so a daemon serving thousands of
    # jobs holds a constant-size trace memory
    _JOB_SPANS = 2048
    _JOB_MAX = 64

    def __init__(self):
        self._lock = threading.Lock()
        self._events: list = []
        self._enabled = False
        self._path = None
        self._pid = os.getpid()
        self._tids: dict = {}        # thread ident -> small tid
        self._lanes: dict = {}       # lane name -> virtual tid
        self._job_capture = False
        self._by_job: OrderedDict = OrderedDict()  # job -> deque(ev)
        self._evicted = 0            # jobs dropped from the LRU

    # -- gating --------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self._enabled or bool(os.environ.get("RACON_TPU_TRACE"))

    @property
    def capturing(self) -> bool:
        """True when events should be recorded at all: a trace output
        is configured OR the per-job index is on (serve daemon)."""
        return self._job_capture or self.enabled

    def enable(self, path: str) -> None:
        self._enabled = True
        self._path = path

    def enable_job_capture(self) -> None:
        """Keep a bounded per-job slice of every tagged event even
        with no trace output path configured — the serve daemon's
        ``submit --trace`` / ``inspect`` source."""
        self._job_capture = True

    def out_path(self):
        return self._path or os.environ.get("RACON_TPU_TRACE") or None

    # -- recording -----------------------------------------------------

    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            tid = self._tids.get(ident)
            if tid is None:
                tid = self._tids[ident] = len(self._tids) + 1
                # name metadata only matters to the full-trace file;
                # job-capture-only mode must not grow _events at all
                if self._enabled or os.environ.get("RACON_TPU_TRACE"):
                    self._events.append({
                        "name": "thread_name", "ph": "M",
                        "pid": self._pid, "tid": tid,
                        "args": {"name":
                                 threading.current_thread().name}})
        return tid

    def _lane_tid(self, lane: str) -> int:
        with self._lock:
            tid = self._lanes.get(lane)
            if tid is None:
                tid = self._lanes[lane] = \
                    self._LANE_TID0 + len(self._lanes)
                if self._enabled or os.environ.get("RACON_TPU_TRACE"):
                    self._events.append({
                        "name": "thread_name", "ph": "M",
                        "pid": self._pid, "tid": tid,
                        "args": {"name": lane}})
        return tid

    @staticmethod
    def _jobs_of(args, jobs):
        """Job ids an event should be indexed under: an explicit
        ``jobs`` list wins (fused dispatches span several jobs), else
        the context-tagged ``args["job"]``."""
        if jobs:
            return [int(j) for j in jobs]
        if args and "job" in args:
            return [int(args["job"])]
        return None

    def _store(self, ev, jobs) -> None:
        """Append to the full buffer (tracing on) and/or the bounded
        per-job index (job capture on).  O(1); never grows the full
        buffer when only the daemon's job capture is active."""
        with self._lock:
            if self._enabled or os.environ.get("RACON_TPU_TRACE"):
                self._events.append(ev)
            if self._job_capture and jobs:
                for j in jobs:
                    dq = self._by_job.get(j)
                    if dq is None:
                        dq = self._by_job[j] = \
                            deque(maxlen=self._JOB_SPANS)
                        while len(self._by_job) > self._JOB_MAX:
                            self._by_job.popitem(last=False)
                            self._evicted += 1
                    dq.append(ev)

    def add_span(self, name: str, t0: float, t1: float,
                 cat: str = "host", lane: str = None,
                 args: dict = None, jobs: list = None) -> None:
        """Record an already-measured [t0, t1] interval (monotonic
        seconds) — the watcher-thread path, and the retroactive path
        for loops that already keep their own marks."""
        if not self.capturing:
            return
        args = _tag_args(args)
        jobs = self._jobs_of(args, jobs)
        if not self.enabled and not jobs:
            return
        tid = self._lane_tid(lane) if lane else self._tid()
        ev = {"name": name, "ph": "X", "cat": cat, "pid": self._pid,
              "tid": tid, "ts": _us(t0),
              "dur": max(0.0, (t1 - t0) * 1e6)}
        if args:
            ev["args"] = args
        self._store(ev, jobs)

    def add_instant(self, name: str, cat: str = "host",
                    args: dict = None, jobs: list = None) -> None:
        if not self.capturing:
            return
        args = _tag_args(args)
        jobs = self._jobs_of(args, jobs)
        if not self.enabled and not jobs:
            return
        ev = {"name": name, "ph": "i", "s": "t", "cat": cat,
              "pid": self._pid, "tid": self._tid(), "ts": _us(now())}
        if args:
            ev["args"] = args
        self._store(ev, jobs)

    def add_flow(self, name: str, flow_id: int, phase: str,
                 cat: str = "fuse", lane: str = None, t: float = None,
                 args: dict = None, jobs: list = None) -> None:
        """Chrome flow event: ``phase`` is "s" (start), "t" (step) or
        "f" (finish); same ``flow_id`` links the arrows.  Used by the
        device executor to tie a tenant's unit-submit span to the
        shared fused-dispatch span ("whose work rode this
        megabatch").  ``bp: "e"`` binds the finish to the enclosing
        span rather than the next one, which is what makes the arrow
        land on the dispatch span itself."""
        if not self.capturing:
            return
        args = _tag_args(args)
        jobs = self._jobs_of(args, jobs)
        if not self.enabled and not jobs:
            return
        tid = self._lane_tid(lane) if lane else self._tid()
        ev = {"name": name, "ph": phase, "cat": cat, "pid": self._pid,
              "tid": tid, "id": int(flow_id),
              "ts": _us(t if t is not None else now())}
        if phase == "f":
            ev["bp"] = "e"
        if args:
            ev["args"] = args
        self._store(ev, jobs)

    def job_slice(self, job_id) -> list:
        """The bounded per-job event list for ``job_id`` (ts-sorted
        copies) — empty when unknown or evicted."""
        with self._lock:
            dq = self._by_job.get(int(job_id))
            evs = [dict(ev) for ev in dq] if dq else []
        evs.sort(key=lambda ev: ev.get("ts", 0.0))
        return evs

    def capture_stats(self) -> dict:
        """Depth/rollover counters for the per-job capture index —
        surfaced through ``health`` so a fleet assembler can warn
        when a job's slice was evicted before collection."""
        with self._lock:
            return {"job_capture": self._job_capture,
                    "jobs": len(self._by_job),
                    "max_jobs": self._JOB_MAX,
                    "spans_per_job": self._JOB_SPANS,
                    "evicted": self._evicted}

    # -- output --------------------------------------------------------

    def write(self, path: str = None) -> str:
        """Serialize the buffer as Chrome trace-event JSON (Perfetto /
        chrome://tracing loadable).  Returns the path written."""
        path = path or self.out_path()
        if not path:
            raise ValueError("no trace output path configured")
        with self._lock:
            events = list(self._events)
        doc = {
            "traceEvents": [{"name": "process_name", "ph": "M",
                             "pid": self._pid, "tid": 0,
                             "args": {"name": "racon-tpu"}}] + events,
            "displayTimeUnit": "ms",
        }
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._tids.clear()
            self._lanes.clear()
            self._by_job.clear()
            self._evicted = 0


TRACER = Tracer()


def enable_trace(path: str) -> None:
    """Turn tracing on for this process, writing to ``path`` (also
    exported as RACON_TPU_TRACE so child contexts agree)."""
    os.environ["RACON_TPU_TRACE"] = path
    TRACER.enable(path)


def write_trace(path: str = None) -> str:
    return TRACER.write(path)


def _annotation(name: str, args: dict):
    """``jax.profiler.TraceAnnotation(name, **args)`` once jax is
    imported (this module never imports it), else None.  Outside a
    profile the annotation costs about a microsecond."""
    if "jax" not in sys.modules:
        return None
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **(args or {}))


class span:
    """Timed block: one pair of clock reads feeds every output.

    * a concurrent JAX profile gets a ``TraceAnnotation`` of the same
      name on the profiler's clock (nvprof-range analog,
      src/cuda/cudapolisher.cpp:66-70), unless ``profile=False``:
      spans of pool workers stay out of the profile, where they would
      name device idle gaps after whatever a pool thread happened to
      be doing;
    * the Chrome-JSON buffer and the serve per-job capture get the
      span when recording is on (:meth:`Tracer.add_span`);
    * with ``metric`` the elapsed seconds accumulate into
      ``registry`` (default: the global registry), whether or not
      anything is recording.

    ``seconds`` holds the elapsed time after the block, for callers
    that keep their own tallies."""

    __slots__ = ("name", "cat", "args", "metric", "registry", "_ann",
                 "_t0", "seconds")

    def __init__(self, name: str, cat: str = "host", args: dict = None,
                 metric: str = None, registry=None,
                 profile: bool = True):
        self.name, self.cat, self.args = name, cat, args
        self.metric, self.registry = metric, registry
        self._ann = _annotation(name, args) if profile else None
        self.seconds = 0.0

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = now()
        return self

    def __exit__(self, *exc):
        t1 = now()
        self.seconds = t1 - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self.metric is not None:
            registry = self.registry
            if registry is None:
                from racon_tpu.obs.metrics import REGISTRY as registry
            registry.add(self.metric, self.seconds)
        TRACER.add_span(self.name, self._t0, t1, cat=self.cat,
                        args=self.args)
        return False
