"""Host stages (parse, map, breaking-point decode, fragment, stitch):
the program's ``host.stage_s`` gauge, summed over the traced
contigs, per Mbp of draft polished."""


def read(ctx):
    v = ctx["registry"].get("host.stage_s")
    return None if v is None else v / ctx["draft_mbp"]
