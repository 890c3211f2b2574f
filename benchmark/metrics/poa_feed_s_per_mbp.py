"""Host work feeding the POA engine: the program's
``poa_phase_s.export`` (packing megabatches) plus
``poa_phase_s.extract`` (consensus bytes out of the results), summed
over the traced contigs, per Mbp of draft polished."""


def read(ctx):
    reg = ctx["registry"]
    pack = reg.get("poa_phase_s.export")
    extract = reg.get("poa_phase_s.extract")
    if pack is None or extract is None:
        return None
    return (pack + extract) / ctx["draft_mbp"]
