"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time, the traced window, device time
per operation, and the longest idle gaps named by the
host span open across them.

Device planes are ``/device:TPU:<n>``.  On each, the ``XLA Ops`` line
holds one event per operation that ran (named by the HLO text, cut
here to the operation's name; operations inside a loop are events of
their own, nested in the loop's); busy time is the union of those
intervals inside the window.  Host spans are the ``TraceAnnotation`` events on the host plane: the
benchmark's own (``bench.*``) and the program's (``racon_tpu.*``).
The window is the benchmark's ``bench.window`` span.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_HOST_SPAN = re.compile(r"^(bench|racon_tpu)\.")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def op_name(name: str) -> str:
    """An operation's name without its HLO text: ``%_poa_full.1 = (...)
    custom-call(...)`` -> ``%_poa_full.1``."""
    return name.split(" = ", 1)[0]


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            for ev in line.events:
                yield (op_name(ev.name), float(ev.start_ns),
                       float(ev.end_ns))


def host_spans(planes):
    """(name, start_ns, end_ns) of every bench.* / racon_tpu.* span on
    any host plane."""
    out = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if _HOST_SPAN.match(ev.name):
                    out.append((ev.name, float(ev.start_ns),
                                float(ev.end_ns)))
    return out


def reduce_planes(planes, top: int = 10) -> dict:
    """The reduction of one trace.  ``planes`` is iterable twice (a
    list of objects with ``name``/``lines``/``events`` as
    ``jax.profiler.ProfileData`` gives them)."""
    planes = list(planes)
    spans = host_spans(planes)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    t0, t1 = min(s for s, _ in windows), max(e for _, e in windows)
    devices = [p for p in planes if _DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("the trace holds no /device:TPU plane")
    ops = defaultdict(float)
    busy = []
    gaps = []
    for plane in devices:
        ivals = []
        for name, s, e in _events(plane, OPS_LINE):
            if e > t0 and s < t1:
                ops[name] += (min(e, t1) - max(s, t0)) * 1e-9
                ivals.append((s, e))
        merged = _union(_clip(ivals, t0, t1))
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    named_gaps = [[_span_at(spans, (s + e) / 2), (e - s) * 1e-9]
                  for s, e in gaps[:top]]
    n = len(devices)
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": sum(busy) / n,
        "chips": n,
        "ops_s": dict(ops),
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": named_gaps,
    }


def _span_at(spans, t):
    """The innermost (shortest) host span open at ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and name != WINDOW_SPAN and \
                (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "no host span"


def reduce_file(path: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, top)


def device_seconds(reduction: dict, patterns) -> float | None:
    """Summed device seconds of the operations whose name matches any
    of ``patterns`` (regular expressions), averaged over chips; None
    where no operation matches (the kernel did not run in the
    window)."""
    rx = [re.compile(p) for p in patterns]
    hits = [v for k, v in reduction["ops_s"].items()
            if any(r.search(k) for r in rx)]
    if not hits:
        return None
    return sum(hits) / reduction["chips"]
