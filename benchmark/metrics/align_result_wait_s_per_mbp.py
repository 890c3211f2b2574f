"""The align rungs' dispatch loop blocked on device results: the
program's ``align.wait_s``, summed over the traced contigs, per Mbp of
draft polished."""


def read(ctx):
    v = ctx["registry"].get("align.wait_s")
    return None if v is None else v / ctx["draft_mbp"]
