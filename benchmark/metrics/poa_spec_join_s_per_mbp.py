"""The POA stage's wait for speculation: the program's
``poa.spec_join_s`` (the ``racon_tpu.poa_spec_join`` span, from the
stage's start to the speculative consumer's last megabatch collected;
the CPU POA lane starts only after it), summed over the traced
contigs, per Mbp of draft polished."""


def read(ctx):
    v = ctx["registry"].get("poa.spec_join_s")
    return None if v is None else v / ctx["draft_mbp"]
