"""Device seconds of the align kernels (``tpu/align_pallas.py``: the
wavefront and banded kernels) in the traced window, from the profiler
trace, per Mbp of draft polished."""

from benchmark import trace_reduce

PATTERNS = (r"^%_wfa_call\b", r"^%_align\b")


def read(ctx):
    tr = ctx.get("trace")
    s = tr and trace_reduce.device_seconds(tr, PATTERNS)
    return None if s is None else s / ctx["draft_mbp"]
