"""How long one lane of the hybrid align split waits for the other:
the distance between the program's ``align.device_lane_end_s`` and
``align.cpu_lane_end_s`` (each lane's last pair, in seconds from the
split), summed over the traced contigs, per Mbp of draft polished."""


def read(ctx):
    reg = ctx["registry"]
    dev = reg.get("align.device_lane_end_s")
    cpu = reg.get("align.cpu_lane_end_s")
    if dev is None or cpu is None:
        return None
    return abs(dev - cpu) / ctx["draft_mbp"]
