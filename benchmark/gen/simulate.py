"""Seeded polishing data: one contig's truth, draft, reads and PAF.

A copy of ``racon_tpu/tools/simulate.py``'s ONT model, kept here so
that a later PR that changes the program cannot change the yardstick.
Extended for the benchmark: every parameter comes from a configuration
file, a genome is cut into independent contigs of equal length, and a
contig's bytes are a function of ``(seed, stream, index)`` alone, so
the pool contigs (stream 0) and the warm-up contigs (stream 1) never
share content and the same seed gives the same bytes.

The model: a homopolymer-enriched random genome, a draft with uniform
substitutions/insertions/deletions at ``draft_error``, and reads with
lognormal lengths (mean ``read_len_mean``), ONT errors at
``read_error`` (half of them homopolymer-run indels) and qualities
that fall near errors.  The PAF gives each read's true placement on
the draft, scaled linearly: seed coordinates, as a mapper would give.
It imports no JAX and nothing of the program.
"""

from __future__ import annotations

import os

import numpy as np

_ACGT = np.frombuffer(b"ACGT", dtype=np.uint8)
_COMPLEMENT = bytes.maketrans(b"ACGT", b"TGCA")
_LOGNORMAL_SIGMA = 0.55
_MIN_OVERLAP = 1000


def contig_rng(seed: int, stream: int, index: int) -> np.random.Generator:
    """The generator of one contig: ``seed`` may be any non-negative
    integer (beyond 64 bits too); ``stream`` 0 is the pool, 1 warm-up."""
    return np.random.default_rng([int(seed), int(stream), int(index)])


def _mutate(seq: np.ndarray, rate: float,
            rng: np.random.Generator) -> np.ndarray:
    """Substitutions, insertions and deletions at ``rate``, split
    evenly (the draft's error model)."""
    keep = rng.random(seq.size) >= rate / 3
    out = seq[keep].copy()
    r2 = rng.random(out.size)
    subs = r2 < rate / 3
    out[subs] = _ACGT[rng.integers(0, 4, int(subs.sum()))]
    ins = np.flatnonzero(r2 >= 1 - rate / 3)
    return np.insert(out, ins + 1, _ACGT[rng.integers(0, 4, ins.size)])


def _mutate_ont(seq: np.ndarray, rate: float, rng: np.random.Generator):
    """ONT-structured errors: half the budget as homopolymer-run
    indels (probability growing with run length), the rest random
    substitutions/insertions/deletions.  Returns (read, err_mask),
    where err_mask marks read positions at or beside an error."""
    bound = np.flatnonzero(np.diff(seq) != 0) + 1
    starts = np.concatenate(([0], bound))
    lens = np.diff(np.concatenate((starts, [seq.size])))
    p_run = np.minimum(rate * 2.0 * np.minimum(lens, 8) / 4.0, 0.9)
    hit = rng.random(lens.size) < p_run
    del_run = hit & (rng.random(lens.size) < 0.5) & (lens > 1)
    ins_run = hit & ~del_run
    keep = np.ones(seq.size, bool)
    keep[starts[del_run]] = False
    out = seq[keep]
    err = np.zeros(out.size, bool)
    old2new = np.cumsum(keep) - 1
    err[np.clip(old2new[starts[del_run]], 0, out.size - 1)] = True
    ins_at = np.clip(old2new[starts[ins_run]], 0, out.size - 1)
    out = np.insert(out, ins_at, out[ins_at])
    err = np.insert(err, ins_at, True)

    rr = rate * 0.5
    keep2 = rng.random(out.size) >= rr / 3
    out2 = out[keep2]
    err2 = err[keep2]
    old2new2 = np.cumsum(keep2) - 1
    err2[np.clip(old2new2[~keep2], 0, max(out2.size - 1, 0))] = True
    r2 = rng.random(out2.size)
    subs = r2 < rr / 3
    out2 = out2.copy()
    out2[subs] = _ACGT[rng.integers(0, 4, int(subs.sum()))]
    err2 |= subs
    ins = np.flatnonzero(r2 >= 1 - rr / 3)
    out2 = np.insert(out2, ins, _ACGT[rng.integers(0, 4, ins.size)])
    err2 = np.insert(err2, ins, True)
    dil = err2.copy()
    dil[1:] |= err2[:-1]
    dil[:-1] |= err2[1:]
    return out2, dil


def _genome(length: int, rng: np.random.Generator) -> np.ndarray:
    """``length`` bases of random sequence in which ~1.5% of positions
    are stretched by geometric extra copies (real genomes carry far
    more long homopolymers than uniform random sequence)."""
    base = _ACGT[rng.integers(0, 4, length)]
    reps = np.ones(length, np.int64)
    sel = rng.random(length) < 0.015
    reps[sel] += rng.geometric(0.45, int(sel.sum()))
    return np.repeat(base, reps)[:length]


def make_contig(cfg: dict, seed: int, stream: int, index: int,
                out_dir: str) -> dict:
    """Write one contig's genome.fasta (truth), draft.fasta,
    reads.fastq and reads2draft.paf under ``out_dir``; returns their
    paths with the draft's and truth's lengths and the read count.

    ``cfg`` is a configuration's ``data`` block: ``contig_len``,
    ``coverage``, ``read_len_mean``, ``read_error``, ``draft_error``.

    The contig is a piece of a longer genome: reads are drawn over the
    contig and flanks of four mean read lengths on each side, and a
    read that overlaps the contig by ``_MIN_OVERLAP`` or more enters
    the PAF with its query range clipped to the overlap (scaled
    linearly, as a mapper's coordinates are approximate).  Coverage
    is therefore flat up to the contig's ends, as it is inside a
    whole-genome assembly that has only the genome's own ends."""
    rng = contig_rng(seed, stream, index)
    os.makedirs(out_dir, exist_ok=True)
    name = f"s{stream}c{index}"
    clen = int(cfg["contig_len"])
    read_len = int(cfg["read_len_mean"])
    flank = 4 * read_len
    ext = _genome(clen + 2 * flank, rng)
    genome = ext[flank:flank + clen]
    draft = _mutate(genome, float(cfg["draft_error"]), rng)
    paths = {k: os.path.join(out_dir, f) for k, f in (
        ("genome", "genome.fasta"), ("draft", "draft.fasta"),
        ("reads", "reads.fastq"), ("paf", "reads2draft.paf"))}
    with open(paths["genome"], "wb") as fh:
        fh.write(b">%s_truth\n%s\n" % (name.encode(), genome.tobytes()))
    with open(paths["draft"], "wb") as fh:
        fh.write(b">%s\n%s\n" % (name.encode(), draft.tobytes()))

    dlen = draft.size
    scale = dlen / clen
    n_draws = ext.size * int(cfg["coverage"]) // read_len
    err_rate = float(cfg["read_error"])
    mu = np.log(read_len) - _LOGNORMAL_SIGMA ** 2 / 2
    rbuf, pbuf = [], []
    for _ in range(n_draws):
        rl = int(np.clip(rng.lognormal(mu, _LOGNORMAL_SIGMA),
                         read_len // 4, read_len * 4))
        start = int(rng.integers(0, ext.size - rl))
        end = start + rl
        ob, oe = max(start, flank), min(end, flank + clen)
        if oe - ob < _MIN_OVERLAP:
            continue
        fwd, errm = _mutate_ont(ext[start:end], err_rate, rng)
        strand = b"+" if rng.random() < 0.5 else b"-"
        n = fwd.size
        qb = int((ob - start) * n / rl)
        qe = int((oe - start) * n / rl)
        data = fwd.tobytes()
        if strand == b"-":
            data = data.translate(_COMPLEMENT)[::-1]
            errm = errm[::-1]
            qb, qe = n - qe, n - qb
        hi = rng.integers(45, 75, n)
        lo = rng.integers(10, 28, n)
        qual = (np.where(errm, lo, hi).astype(np.uint8) + 33).tobytes()
        rname = b"%s_r%06d" % (name.encode(), len(rbuf))
        rbuf.append(b"@%s\n%s\n+\n%s\n" % (rname, data, qual))
        t_begin = int((ob - flank) * scale)
        t_end = min(dlen, int((oe - flank) * scale))
        pbuf.append(b"%s\t%d\t%d\t%d\t%s\t%s\t%d\t%d\t%d\t%d\t%d\t255\n"
                    % (rname, n, qb, qe, strand, name.encode(), dlen,
                       t_begin, t_end, t_end - t_begin, t_end - t_begin))
    with open(paths["reads"], "wb") as fh:
        fh.write(b"".join(rbuf))
    with open(paths["paf"], "wb") as fh:
        fh.write(b"".join(pbuf))
    return {**paths, "name": name, "draft_len": int(dlen),
            "truth_len": int(clen), "reads_n": len(rbuf)}
