"""Breaking-point decode batches queued behind the CPU-lane workers in
the shared thread pool: the program's ``host.bp_decode_queue_s``
(submit to start of each batch, summed), summed over the traced
contigs, per Mbp of draft polished."""


def read(ctx):
    v = ctx["registry"].get("host.bp_decode_queue_s")
    return None if v is None else v / ctx["draft_mbp"]
