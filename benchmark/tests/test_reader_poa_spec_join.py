"""The POA speculation-join reader, in the form of test_readers: the
program's registry turned into seconds per Mbp, and nothing read where
the key is absent (a program without the span)."""

import pytest

from benchmark import run

MBP = 0.4


@pytest.mark.parametrize("value", [0.0, 0.52, 3.38])
def test_poa_spec_join_reader(value):
    read = run.load_reader("poa_spec_join_s_per_mbp")
    other = {"host.stage_s": 9.0, "stage_wall_s.align": 7.0}
    reg = dict(other, **{"poa.spec_join_s": value})
    assert read({"registry": reg, "draft_mbp": MBP}) == \
        pytest.approx(value / MBP)
    # a program without the span records no such key
    assert read({"registry": other, "draft_mbp": MBP}) is None
