"""The control, kept as a test at a size a test run holds: the
configuration's guarantee that every window with three or more layers
is replaced by its consensus, broken the way a later PR might be
tempted to (the CPU POA lane leaves its windows as the draft), comes
out not correct.  On the CPU every window takes the CPU lane.  The
chip readings of the control at the cells' own size are in PERF.md."""

import pytest

from benchmark import limits
from benchmark.tests.test_faults import CELLS, small_run


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(monkeypatch, cell):
    with limits.cpu_lane_draft():
        line = small_run(monkeypatch, cell)
    assert not line["correct"], line["checks"]
    # every window unpolished: racon drops the contig, so the answer
    # is missing rather than wrong
    c = line["checks"]
    assert c["contigs_without_one_sequence"]["value"] >= 1 or \
        c["contig_err_per_100kbp_max"]["value"] > \
        c["contig_err_per_100kbp_max"]["limit"]
