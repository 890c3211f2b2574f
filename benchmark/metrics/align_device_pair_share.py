"""Share of the PAF's read/draft pairs whose alignment finished on a
device rung: admissions over all rungs, less the pairs each rung
handed on (``align_rung_retry.*``) and those that fell through to the
CPU (``align_rung_cpu_fallthrough``), over the pairs in the PAF."""


def read(ctx):
    reg = ctx["registry"]
    if not ctx["paf_pairs"]:
        return None

    def total(prefix):
        return sum(v for k, v in reg.items() if k.startswith(prefix))

    done = (total("align_rung_admit.") - total("align_rung_retry.")
            - reg.get("align_rung_cpu_fallthrough", 0))
    return done / ctx["paf_pairs"]
