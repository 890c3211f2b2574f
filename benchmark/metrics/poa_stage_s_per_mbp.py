"""POA stage wall (device POA and the native CPU lane):
``stage_wall_s.consensus`` summed over the traced contigs, per Mbp of
draft polished."""


def read(ctx):
    v = ctx["registry"].get("stage_wall_s.consensus")
    return None if v is None else v / ctx["draft_mbp"]
