"""The feed-loop readers: each turns the program's registry into
seconds per Mbp, and reads nothing where its keys are absent (a
program without the counters)."""

import pytest

from benchmark import run

MBP = 0.4

# reader -> (registry keys it reads, the value those keys give)
CASES = {
    "lead_in_s_per_mbp": ({"device.lead_in_s": 1.2}, 1.2 / MBP),
    "bp_decode_queue_s_per_mbp": ({"host.bp_decode_queue_s": 0.8},
                                  0.8 / MBP),
    "align_feed_s_per_mbp": ({"align.pack_s": 0.5,
                              "align.decode_s": 1.5}, 2.0 / MBP),
    "align_result_wait_s_per_mbp": ({"align.wait_s": 3.0}, 3.0 / MBP),
    "align_lane_gap_s_per_mbp": ({"align.device_lane_end_s": 4.0,
                                  "align.cpu_lane_end_s": 6.5},
                                 2.5 / MBP),
    "poa_feed_s_per_mbp": ({"poa_phase_s.export": 0.25,
                            "poa_phase_s.extract": 0.75}, 1.0 / MBP),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader(name):
    keys, want = CASES[name]
    read = run.load_reader(name)
    other = {"host.stage_s": 9.0, "stage_wall_s.align": 7.0}
    ctx = {"registry": dict(keys, **other), "draft_mbp": MBP}
    assert read(ctx) == pytest.approx(want)
    # the parent of this change records none of these keys
    assert read({"registry": other, "draft_mbp": MBP}) is None
    # a reader of two keys reads nothing from one of them
    for k in keys:
        partial = {kk: v for kk, v in keys.items() if kk != k}
        if partial:
            assert read({"registry": dict(partial, **other),
                         "draft_mbp": MBP}) is None
