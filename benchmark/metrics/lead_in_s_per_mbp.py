"""Host lead-in before the device is fed: the program's
``device.lead_in_s`` gauge (initialize()'s start to the start of the
first device dispatch: parsing, overlap loading, the divergence probe),
summed over the traced contigs, per Mbp of draft polished."""


def read(ctx):
    v = ctx["registry"].get("device.lead_in_s")
    return None if v is None else v / ctx["draft_mbp"]
