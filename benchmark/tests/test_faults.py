"""A run at a small size, with the harness's look for a chip skipped
and the program's CPU lanes standing in for the device: sound, it
comes out correct; with the timed path broken underneath, not.

Faults a polishing cell can have (step 3 of the benchmark's rules):
a step that returns its state unchanged (every window keeps the
draft), half of the batch left out (every other window keeps the
draft), and a token altered where it is produced (one base of every
window's consensus).  An exchange between chips does not exist on
one chip."""

import jax
import pytest

from benchmark import run

SMALL = {"contig_len": 20_000, "pool_contigs": 2, "warm_variants": []}
CPU_LANES = {"tpu_poa_batches": 0, "tpu_aligner_batches": 0}


def small_run(monkeypatch, cell, seed=2**33 + 1):
    """``run.run_cell`` whole, on the CPU at 20 kb: the look for a chip
    skipped, the configuration cut, its lanes on the CPU."""
    load_cell = run.load_cell

    def small_cell(name):
        bench, c, config, traffic = load_cell(name)
        return bench, c, config | SMALL | {
            "polish": config["polish"] | CPU_LANES}, traffic

    monkeypatch.setattr(run, "load_cell", small_cell)
    monkeypatch.setattr(run, "require_chips",
                        lambda chips: jax.devices()[:chips])
    return run.run_cell(cell, seed, 0.1, False)


def _break(monkeypatch, how):
    from racon_tpu.core.polisher import Polisher

    orig = Polisher.generate_consensuses

    def broken(self):
        flags = orig(self)
        for i, w in enumerate(self.windows):
            if how == "unchanged" or (how == "half" and i % 2):
                w.consensus = w.sequences[0]
            elif how == "token" and w.consensus:
                c = w.consensus
                w.consensus = (b"A" if c[:1] != b"A" else b"C") + c[1:]
        return flags

    monkeypatch.setattr(Polisher, "generate_consensuses", broken)


CELLS = ["ont_r941_ecoli_ci.contigs", "ont_r1041_ecoli.contigs"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(monkeypatch, cell):
    line = small_run(monkeypatch, cell)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1


@pytest.mark.parametrize("how", ["unchanged", "half", "token"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(monkeypatch, cell, how):
    _break(monkeypatch, how)
    line = small_run(monkeypatch, cell)
    assert not line["correct"], line["checks"]
    assert line["failed"] >= 1
