"""The generator: same seed, same bytes; streams never share content."""

import os

import pytest

from benchmark.gen import simulate
from benchmark.run import load_cell

SMALL = {"contig_len": 20_000}


def _cfg(cell="ont_r941_ecoli_ci.contigs"):
    _, _, config, _ = load_cell(cell)
    return config["data"] | SMALL


def _bytes(c):
    return {k: open(c[k], "rb").read()
            for k in ("genome", "draft", "reads", "paf")}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**64 + 3])
def test_same_seed_same_bytes(tmp_path, seed):
    a = simulate.make_contig(_cfg(), seed, 0, 1, str(tmp_path / "a"))
    b = simulate.make_contig(_cfg(), seed, 0, 1, str(tmp_path / "b"))
    assert _bytes(a) == _bytes(b)
    c = simulate.make_contig(_cfg(), seed + 1, 0, 1, str(tmp_path / "c"))
    assert _bytes(c)["genome"] != _bytes(a)["genome"]


def _kmers(seq, k=24):
    return {seq[i:i + k] for i in range(0, len(seq) - k + 1)}


def test_warmup_and_pool_share_no_content(tmp_path):
    cfg = _cfg()
    pool = simulate.make_contig(cfg, 11, 0, 0, str(tmp_path / "p"))
    warm = simulate.make_contig(cfg, 11, 1, 0, str(tmp_path / "w"))
    g = [open(c["genome"], "rb").read().split(b"\n")[1]
         for c in (pool, warm)]
    assert not _kmers(g[0]) & _kmers(g[1])
    assert pool["name"] != warm["name"]


@pytest.mark.parametrize("cell", ["ont_r941_ecoli_ci.contigs",
                                  "ont_r1041_ecoli.contigs"])
def test_contig_matches_configuration(tmp_path, cell):
    cfg = _cfg(cell)
    c = simulate.make_contig(cfg, 3, 0, 0, str(tmp_path))
    genome = open(c["genome"], "rb").read().split(b"\n")[1]
    assert len(genome) == SMALL["contig_len"] == c["truth_len"]
    paf = open(c["paf"], "rb").read().splitlines()
    assert len(paf) == c["reads_n"] > 0
    covered = sum(int(r.split(b"\t")[8]) - int(r.split(b"\t")[7])
                  for r in paf)
    # coverage stays near the configuration's, flat to the contig ends
    assert 0.8 * cfg["coverage"] < covered / c["draft_len"] \
        < 1.2 * cfg["coverage"]
    assert os.path.getsize(c["reads"]) > 0
