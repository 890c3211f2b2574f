"""Device seconds of the POA kernel (``tpu/poa_pallas.py``) in the
traced window, from the profiler trace, per Mbp of draft polished.
The patterns match the kernel's operation names as the trace shows
them; a trace with none of them gives no reading."""

from benchmark import trace_reduce

PATTERNS = (r"^%_poa_full\b",)


def read(ctx):
    tr = ctx.get("trace")
    s = tr and trace_reduce.device_seconds(tr, PATTERNS)
    return None if s is None else s / ctx["draft_mbp"]
