"""The comparison that decides ``correct``.

The plain reference of a polisher is the sequence it estimates: the
simulated truth each contig's reads were drawn from
(``benchmark/gen/simulate.py``), with nothing of the program in it.
Each polished contig timed in the window is aligned to its truth by
an independent edit distance (RapidFuzz's Levenshtein, banded by a
cutoff at 10% of the truth's length, so a reading at the cutoff means
"10% or worse"), and the number compared is its residual error per
100 kbp of truth.  A contig that comes back as anything but one
sequence is a failed answer.  The limit of each configuration is in
its file, under ``correct.limit``; ``PERF.md`` gives the readings it
was set from.
"""

from __future__ import annotations

from rapidfuzz.distance import Levenshtein

CUTOFF_SHARE = 0.10


def read_fasta_one(path: str) -> bytes:
    with open(path, "rb") as f:
        return b"".join(line.strip() for line in f
                        if not line.startswith(b">"))


def edit_distance(a: bytes, b: bytes) -> int:
    """Levenshtein distance of ``a`` and ``b``, or the cutoff + 1 where
    it exceeds 10% of ``b``'s length."""
    cutoff = max(1, int(len(b) * CUTOFF_SHARE))
    return Levenshtein.distance(a.decode("ascii"), b.decode("ascii"),
                                score_cutoff=cutoff)


def compare(results: list, contigs: dict, limit) -> dict:
    """``results``: the window's polishes (``name``, ``polished``, a
    list of sequences); ``contigs``: the generated contigs by name;
    ``limit``: the most residual errors per 100 kbp a contig may
    have (None: not yet set, so nothing is correct)."""
    per_contig = []
    dist_total = truth_total = 0
    failed = 0
    for r in results:
        truth = read_fasta_one(contigs[r["name"]]["genome"])
        if len(r["polished"]) != 1:
            failed += 1
            per_contig.append(None)
            continue
        d = edit_distance(r["polished"][0], truth)
        rate = d / len(truth) * 1e5
        per_contig.append(rate)
        dist_total += d
        truth_total += len(truth)
        if limit is None or rate > limit:
            failed += 1
    worst = max((x for x in per_contig if x is not None), default=None)
    missing = sum(x is None for x in per_contig)
    return {
        "correct": bool(results) and failed == 0,
        "failed": failed,
        "per_contig": per_contig,
        "residual_err_per_100kbp": (dist_total / truth_total * 1e5
                                    if truth_total else None),
        "checks": {
            "contig_err_per_100kbp_max": {"value": worst,
                                          "limit": limit},
            "contigs_without_one_sequence": {"value": missing,
                                             "limit": 0},
        },
    }
