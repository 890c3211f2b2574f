"""Align stage wall (device WFA/banded rungs and the CPU lane):
``stage_wall_s.align`` summed over the traced contigs, per Mbp of
draft polished."""


def read(ctx):
    v = ctx["registry"].get("stage_wall_s.align")
    return None if v is None else v / ctx["draft_mbp"]
