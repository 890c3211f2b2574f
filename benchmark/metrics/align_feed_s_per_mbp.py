"""Host work of the align rungs' dispatch loop: the program's
``align.pack_s`` (cache keying, encoding, enqueue) plus
``align.decode_s`` (tapes and moves to runs), summed over the traced
contigs, per Mbp of draft polished."""


def read(ctx):
    reg = ctx["registry"]
    pack, decode = reg.get("align.pack_s"), reg.get("align.decode_s")
    if pack is None or decode is None:
        return None
    return (pack + decode) / ctx["draft_mbp"]
