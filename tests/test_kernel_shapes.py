"""Shape policy helpers of the flagship kernels (pure functions).

These pins make cold-start coverage auditable: prewarm/prebuild must
predict the exact batch a dispatch will run (racon_tpu/tpu/
poa_pallas.py padded_batch), and the windows-per-program selection
decides which configurations the flagship kernel serves at all.
"""

import pytest

from racon_tpu.tpu import align_pallas, poa_pallas


@pytest.fixture(autouse=True)
def _no_kernel_overrides(monkeypatch):
    # a developer's exported RACON_TPU_POA_SWIN / _KRANK must not fail
    # the stock-policy pins; the override tests set them explicitly
    monkeypatch.delenv("RACON_TPU_POA_SWIN", raising=False)
    monkeypatch.delenv("RACON_TPU_POA_KRANK", raising=False)


def test_windows_per_program_stock_configs():
    # the SMEM model counts what the v5e compiler allocates, lane
    # padding of the double-buffered meta/mout blocks included (PR 21:
    # the compiler refused S=5 at d1=64 by 656 bytes); every pick
    # below compiles (tests/test_tpu_compile.py)
    wb500 = poa_pallas.band_width(1024)
    assert wb500 == 256
    assert poa_pallas.pick_windows_per_program(
        2048, 1024, 32, 16, 16, 8, wb500) == 5
    # deep megabatches (d1=64: windows deeper than 31 layers, common
    # at 30x ONT) drop to four: the meta block is 64K per window
    assert poa_pallas.pick_windows_per_program(
        2048, 1024, 64, 16, 16, 8, wb500) == 4
    # shallow ones fit six
    assert poa_pallas.pick_windows_per_program(
        2048, 1024, 16, 16, 16, 8, wb500) == 6
    wb1000 = poa_pallas.band_width(2048)
    assert wb1000 == 512
    assert poa_pallas.pick_windows_per_program(
        4096, 2048, 32, 16, 16, 8, wb1000) == 3
    assert poa_pallas.pick_windows_per_program(
        4096, 2048, 64, 16, 16, 8, wb1000) == 2
    # the banded w=1000 band (256 cols) picks the same (SMEM binds,
    # not the band-width-dependent VMEM)
    wb1000b = poa_pallas.band_width(2048, banded=True)
    assert wb1000b == 256
    assert poa_pallas.pick_windows_per_program(
        4096, 2048, 32, 16, 16, 8, wb1000b) == 3


def test_smem_model_matches_the_compiler():
    # the v5e compiler's own counts (PR 21 rehearsals, b=65 / b=256):
    # S=5 at d1=64 used 1.00M + 656 B, S=4 at d1=128 1.00M + 61.1K;
    # the model sits within the reserve below both
    for v, lp, d1, s_win, used in ((2048, 1024, 64, 5, (1 << 20) + 656),
                                   (2048, 1024, 128, 4,
                                    (1 << 20) + 62566)):
        model = poa_pallas._smem_bytes(v, lp, d1, s_win)
        assert used - poa_pallas._SMEM_RESERVE < model <= used


def test_rank_unroll_stock_configs():
    # multi-rank stepping: both stock shapes take the full 4-rank
    # unroll next to their windows-per-program pick
    assert poa_pallas.pick_rank_unroll(
        2048, 1024, 32, 16, 16, 8, 256, s_win=5) == 4
    assert poa_pallas.pick_rank_unroll(
        4096, 2048, 32, 16, 16, 8, 512, s_win=2) == 4
    # no flagship kernel -> no unroll decision to make
    assert poa_pallas.pick_rank_unroll(
        2048, 1024, 32, 16, 16, 8, 256, s_win=0) == 4
    assert poa_pallas.pick_rank_unroll(
        2048, 1024, 32, 16, 16, 8, 256, s_win=-1) == 1


def test_windows_per_program_env_override(monkeypatch):
    monkeypatch.setenv("RACON_TPU_POA_SWIN", "2")
    assert poa_pallas.pick_windows_per_program(
        2048, 1024, 32, 16, 16, 8, 256) == 2
    # a forced factor that does not fit reports 0 (caller falls back)
    # and WARNS instead of silently routing to the lockstep engine
    monkeypatch.setenv("RACON_TPU_POA_SWIN", "8")
    with pytest.warns(RuntimeWarning, match="RACON_TPU_POA_SWIN"):
        assert poa_pallas.pick_windows_per_program(
            2048, 1024, 32, 16, 16, 8, 256) == 0


def test_windows_per_program_env_validation(monkeypatch):
    # malformed values fail loudly, naming the variable
    monkeypatch.setenv("RACON_TPU_POA_SWIN", "three")
    with pytest.raises(ValueError, match="RACON_TPU_POA_SWIN"):
        poa_pallas.pick_windows_per_program(2048, 1024, 32)
    monkeypatch.setenv("RACON_TPU_POA_SWIN", "0")
    with pytest.raises(ValueError, match="RACON_TPU_POA_SWIN"):
        poa_pallas.pick_windows_per_program(2048, 1024, 32)


def test_rank_unroll_env_override(monkeypatch):
    monkeypatch.setenv("RACON_TPU_POA_KRANK", "2")
    assert poa_pallas.pick_rank_unroll(
        2048, 1024, 32, 16, 16, 8, 256, s_win=5) == 2
    # a forced unroll the budget rejects warns and falls back to the
    # policy pick instead of disabling the kernel
    monkeypatch.setenv("RACON_TPU_POA_KRANK", "8")
    with pytest.warns(RuntimeWarning, match="RACON_TPU_POA_KRANK"):
        assert poa_pallas.pick_rank_unroll(
            2048, 1024, 32, 16, 16, 8, 256, s_win=5) == 4
    monkeypatch.setenv("RACON_TPU_POA_KRANK", "nope")
    with pytest.raises(ValueError, match="RACON_TPU_POA_KRANK"):
        poa_pallas.pick_rank_unroll(2048, 1024, 32, s_win=5)


def test_padded_batch_matches_dispatch_multiples():
    # w=500 class: s_win=5, one device -> multiples of 5
    for b, want in ((64, 65), (32, 35), (256, 260), (65, 65)):
        assert poa_pallas.padded_batch(b, 1, 2048, 1024, 32) == want
    # w=1000 class: s_win=3 -> multiples of 3
    assert poa_pallas.padded_batch(
        32, 1, 4096, 2048, 32, wb=512) == 33
    assert poa_pallas.padded_batch(
        30, 1, 4096, 2048, 32, wb=512) == 30
    # mesh multiple folds in
    assert poa_pallas.padded_batch(64, 8, 2048, 1024, 32) == 80


def test_align_pad_pairs_floor():
    # floor 32 bounds the compiled-variant set (manifest coverage)
    assert align_pallas.pad_pairs(1) == 32
    assert align_pallas.pad_pairs(8) == 32
    assert align_pallas.pad_pairs(33) == 64
    assert align_pallas.pad_pairs(128) == 128
    # mesh multiple preserved
    assert align_pallas.pad_pairs(40, 8) % (8 * 8) == 0
    # ...but never past the chunk cap when empty lanes cost real HBM
    # (one WFA pair at lq=16384, emax=2048 holds ~0.27 GB)
    big = align_pallas.wfa_per_pair_bytes(16384, 2048)
    assert align_pallas.chunk_pairs(big) == 8
    assert align_pallas.pad_pairs(1, 1, big) == 8
    assert align_pallas.pad_pairs(9, 1, big) == 16


@pytest.mark.parametrize("lq,emax,measured", [
    # temp + args + outputs per pair from the v5e compiler's
    # memory_analysis (PR 21 rehearsals, worst over 8-256 pairs)
    (10112, 512, 40205594),
    (12032, 1024, 91109388),
    (16384, 512, 65332748),
    (16384, 1024, 123383312),
    (16384, 2048, 248387712),
])
def test_wfa_per_pair_bytes_bounds_the_compiler(lq, emax, measured):
    model = align_pallas.wfa_per_pair_bytes(lq, emax)
    assert measured <= model <= 1.15 * measured


def test_chunk_pairs_fits_the_budget(monkeypatch):
    monkeypatch.delenv("RACON_TPU_ALIGN_BUDGET", raising=False)
    monkeypatch.delenv("RACON_TPU_PIPE_DEPTH", raising=False)
    per_chunk = align_pallas.ALIGN_BUDGET // 2
    for lq in (8192, 16384):
        for emax in (512, 1024, 2048):
            pp = align_pallas.wfa_per_pair_bytes(lq, emax)
            n = align_pallas.chunk_pairs(pp)
            assert n >= 8 and n & (n - 1) == 0
            assert n == 8 or n * pp <= per_chunk
    # the mesh multiplies pairs, not bytes per device
    pp = align_pallas.wfa_per_pair_bytes(16384, 1024)
    assert align_pallas.chunk_pairs(pp, 4) == \
        4 * align_pallas.chunk_pairs(pp)
    # a deeper pipeline halves each chunk
    monkeypatch.setenv("RACON_TPU_PIPE_DEPTH", "4")
    assert align_pallas.chunk_pairs(pp) == 8
