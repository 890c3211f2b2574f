"""1 - (union of device operation intervals) / (traced window),
averaged over the chips used, from the profiler trace."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
