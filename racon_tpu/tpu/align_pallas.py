"""Single-dispatch batched pairwise alignment: Pallas TPU kernel.

Replaces the lax.scan wavefront kernels (racon_tpu/tpu/aligner.py) on
real TPU backends.  The scan kernels pay per-step XLA overhead over
``lq+lt`` anti-diagonals and one host round-trip per (bucket, chunk).
This kernel aligns EVERY queued pair in one ``pallas_call`` and emits
a compact 2-bit move tape.

Design notes:

* **8 pairs per grid program, stacked on the sublane axis** (a full
  8-sublane vreg): the banded row DP's critical path is the in-row
  prefix-min chain (log2(wb) serial vector steps, latency-bound
  regardless of width), so eight independent pairs share ONE chain
  per row group -- measured 0.57-0.96 us/row vs ~2 us single-pair.
  Callers sort pairs by length so group partners finish together;
* the row loop bound is the group's longest REAL query, so mixing
  short and long pairs in one shape bucket costs padding memory, not
  padded compute -- no per-length bucket dispatch loop (the
  cudaaligner analog queues per-batch, src/cuda/cudaaligner.cpp:52-86);
* the band follows a per-pair CENTER TABLE: piecewise-linear knots
  (one per ``_CTR_BLK`` rows, scalar-prefetched) give the expected
  target column at each query row, quantized to 128 columns so the
  per-row target slice and the previous-row realignment are
  lane-aligned (TPU dynamic lane offsets must be 128-multiples).  The
  default knots reproduce the proportional diagonal ``i*tl/ql``, for
  which an alignment of cost c deviates at most ``(c + |tl-ql|)/2``
  columns, so a tape satisfying ``cost + |tl-ql| <= wb - 512`` is
  exact (Ukkonen) and callers escalate the rest to a wider band.
  Retry pairs instead follow MEASURED knots from a strided k-mer
  pre-pass (``estimate_center_knots``), so a band of the same width
  can hold alignments with large indel drift; those results are
  accepted on the empirical criterion that the recovered path keeps
  >= one 128-column quantum of margin to both band edges
  (``path_center_margin``), not the Ukkonen certificate;
* no direction tape is materialised in HBM: the forward pass keeps
  one score-row checkpoint every ``_CKPT`` rows in VMEM, and the
  traceback re-derives each 128-row block's directions from its
  checkpoint on demand, walking all stacked pairs' segments through a
  block before moving down (one recompute per block, not per pair);
* the kernel emits 2-bit moves (diag/up/left) packed 16-per-int32;
  the host reconstructs =/X from the sequences vectorised, then RLEs
  to a CIGAR (the reference also finishes CIGARs on the host,
  src/cuda/cudaaligner.cpp:89-103).
"""

from __future__ import annotations

import functools
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from racon_tpu.obs import decision as _decision
from racon_tpu.obs import devutil as obs_devutil
from racon_tpu.obs import trace as obs_trace

# the sanctioned clock (racon_tpu/obs): watcher spans feed only the
# trace and the device_s reporting counters, never control flow
_mono = obs_trace.now

_BIG = 1 << 20
_CKPT = 128                  # rows between score checkpoints
                             # (halved for wide bands: VMEM dirs block)


def _ckrows(wb: int) -> int:
    """Rows per checkpoint block, shrunk for wide bands so the dirs
    scratch (ckrows x 8 x wb i32) stays inside the ~16 MB VMEM scope."""
    if wb >= 8192:
        return 32
    return 64 if wb >= 4096 else _CKPT
_N_SHIFT = 3                 # band start advances <= 2 quanta per row
_S = 8                       # pairs stacked per grid program
_MV_DIAG, _MV_UP, _MV_LEFT = 0, 1, 2

# center-table knot spacing (rows); 16384-cap rows -> <= 18 knots/pair
_CTR_BLK = 1024
_CTR_LOG = 10


def _n_ctr(lq: int) -> int:
    """Knots per pair for a query bucket (row i reads knots i>>10 and
    (i>>10)+1, so one knot past the last full block plus one)."""
    return lq // _CTR_BLK + 2


# per-row center advance cap: the kernel realigns the previous row by
# at most _N_SHIFT-1 = 2 quanta (256 columns), so a knot segment may
# advance at most 255 columns per row
_CTR_SLOPE_MAX = 255


def proportional_knots(ql: int, tl: int, lq: int) -> np.ndarray:
    """Default center table: the proportional diagonal ``i*tl/ql``
    sampled at the knot rows.  Knot values PAST the query length
    keep the slope (they may exceed tl -- rows stop at ql, and the
    kernel clips band starts): clipping them to tl would flatten the
    interpolated center across the final block and mis-place the
    band at the end of every pair shorter than its knot grid."""
    ks = np.arange(_n_ctr(lq), dtype=np.int64) * _CTR_BLK
    vals = (ks * tl) // max(ql, 1)
    return np.minimum(vals,
                      ks * _CTR_SLOPE_MAX + tl).astype(np.int32)


def smooth_knots(knots: np.ndarray, tl: int) -> np.ndarray:
    """Clamp a measured center path into kernel-legal knots: monotone
    non-decreasing with each segment advancing at most
    ``_CTR_SLOPE_MAX`` columns per row (the kernel's 2-quanta
    realignment window), values bounded but NOT clipped to tl (see
    proportional_knots)."""
    k = np.maximum.accumulate(np.clip(
        knots, 0, tl + _CTR_SLOPE_MAX * _CTR_BLK).astype(np.int64))
    d = np.clip(np.diff(k), 0, _CTR_SLOPE_MAX * _CTR_BLK)
    return np.concatenate(
        ([k[0]], k[0] + np.cumsum(d))).astype(np.int32)


def estimate_center_knots(query: bytes, target: bytes,
                          lq: int) -> np.ndarray:
    """Cheap strided pre-pass estimating the pair's REAL diagonal
    path: at every knot row an exact query 16-mer is looked up in a
    rolling-hash index of the target and the hit nearest the previous
    knot's extrapolation wins; missing knots interpolate.  The result
    (smoothed monotone) replaces the proportional diagonal for retry
    pairs whose indel drift pushed the true path out of a
    proportionally-centered band — measured centers let the SAME band
    width hold the alignment instead of escalating rungs."""
    k = 16
    ql, tl = len(query), len(target)
    prop = proportional_knots(ql, tl, lq)
    if ql < 4 * k or tl < 4 * k:
        return prop
    qa = np.frombuffer(query, np.uint8).astype(np.uint64)
    ta = np.frombuffer(target, np.uint8).astype(np.uint64)
    mul = np.uint64(1099511628211)      # FNV-ish rolling base

    def hashes(a):
        h = np.zeros(len(a) - k + 1, np.uint64)
        for p in range(k):
            h = h * mul + a[p:p + len(h)]
        return h
    hq, ht = hashes(qa), hashes(ta)
    n_ctr = _n_ctr(lq)
    knots = np.full(n_ctr, -1, np.int64)
    knots[0] = 0
    slope = tl / max(ql, 1)
    prev_row, prev_col = 0, 0
    for ki in range(1, n_ctr):
        row = ki * _CTR_BLK
        if row >= ql - k:
            break
        cand = np.flatnonzero(ht == hq[row])
        if cand.size:
            expect = prev_col + (row - prev_row) * slope
            j = int(cand[np.argmin(np.abs(cand - expect))])
            knots[ki] = j
            prev_row, prev_col = row, j
    # tail + gaps: extend/interpolate along the proportional slope
    last = -1
    for ki in range(n_ctr):
        if knots[ki] >= 0:
            last = ki
    for ki in range(n_ctr):
        if knots[ki] < 0:
            knots[ki] = (knots[last] + (ki - last) * _CTR_BLK * slope
                         if last >= 0 and ki > last else prop[ki])
    return smooth_knots(knots, tl)


def path_center_margin(moves_row: np.ndarray, length: int,
                       knots: np.ndarray, wb: int) -> int:
    """Smallest distance (columns) from the decoded path to either
    edge of the knot-centered band — the empirical acceptance
    criterion for re-centered rungs (a path that never comes within a
    quantum of the band edge would not change under widening)."""
    mv = moves_row[:length][::-1]
    di = np.cumsum((mv != _MV_LEFT).astype(np.int64))      # i after op
    dj = np.cumsum((mv != _MV_UP).astype(np.int64))        # j after op
    kk = di >> _CTR_LOG
    kn = knots.astype(np.int64)
    c0 = kn[np.minimum(kk, len(kn) - 1)]
    c1 = kn[np.minimum(kk + 1, len(kn) - 1)]
    ctr = c0 + (((c1 - c0) * (di & (_CTR_BLK - 1))) >> _CTR_LOG)
    dev = int(np.max(np.abs(dj - ctr))) if len(mv) else 0
    return wb // 2 - dev


def available() -> bool:
    """Default on real TPU backends (RACON_TPU_PALLAS_ALIGN=0 falls
    back to the scan-ladder kernels): with 8 pairs sharing each row
    group the kernel measures 0.57-0.96 us/row including the
    traceback pass, in ONE dispatch per band rung."""
    if os.environ.get("RACON_TPU_NO_PALLAS"):
        return False
    if os.environ.get("RACON_TPU_PALLAS_ALIGN", "1") == "0":
        return False
    if os.environ.get("RACON_TPU_PALLAS_INTERPRET") == "1":
        return True
    # a backend that fails to start raises: it must not quietly
    # select the scan-ladder kernels
    return jax.devices()[0].platform == "tpu"


def _kernel(ql_ref, tl_ref, ctr_ref, q_ref, t_ref, tape_ref, dist_ref,
            ckpt_hbm, ckstage, dirs, taperow, dsem, regs_s, *,
            lq: int, lt: int, wb: int, ckrows: int):
    g0 = pl.program_id(0) * _S
    nck8 = (lq // ckrows + 1) * 8
    ck0 = pl.program_id(0) * nck8      # this program's HBM region
    q = 128
    n_ctr = _n_ctr(lq)
    tape_w = (lq + lt) // 16 + 1
    tape_rows = (tape_w + 127) // 128
    big = jnp.int32(_BIG)
    cols = lax.broadcasted_iota(jnp.int32, (1, wb), 1)
    cols_s = lax.broadcasted_iota(jnp.int32, (_S, wb), 1)
    rows_s = lax.broadcasted_iota(jnp.int32, (_S, wb), 0)
    iota_c = lax.broadcasted_iota(jnp.int32, (1, 128), 1)

    qls = [ql_ref[g0 + s] for s in range(_S)]
    tls = [tl_ref[g0 + s] for s in range(_S)]
    smaxs = [(jnp.maximum(tls[s] + 1 - wb, 0) + q - 1) // q
             for s in range(_S)]

    def sqq(s, i):
        """Quantized band start for pair s, row i: centered on the
        pair's knot-interpolated center table (symmetric margins
        >= wb/2 - 128).  The knots are host-built: the proportional
        diagonal by default, a measured diagonal path for re-centered
        retry rungs (estimate_center_knots).  Host smoothing bounds
        the knot slope so consecutive-row starts move <= 1 quantum,
        inside the _N_SHIFT realignment window.  Cost per call: two
        SMEM loads + one multiply/shift, on par with the fixed-point
        slope multiply this replaces."""
        k = i >> _CTR_LOG
        c0 = ctr_ref[(g0 + s) * n_ctr + k]
        c1 = ctr_ref[(g0 + s) * n_ctr + k + 1]
        ctr_i = c0 + (((c1 - c0) * (i - (k << _CTR_LOG))) >> _CTR_LOG)
        return jnp.clip((ctr_i - (wb // 2)) >> 7, 0, smaxs[s])

    def stackv(vals, dtype=jnp.int32):
        """[_S] scalars -> [_S, 1] column vector."""
        out = jnp.full((_S, 1), 0, dtype)
        ri = lax.broadcasted_iota(jnp.int32, (_S, 1), 0)
        for s, v in enumerate(vals):
            out = jnp.where(ri == s, jnp.asarray(v, dtype), out)
        return out

    # tl as a broadcastable column; per-pair big mask rows beyond tl
    tl_col = stackv(tls)

    def t_band(starts):
        """Stacked [S, wb] target chars at each pair's band start."""
        rows = [t_ref[s, :, pl.ds(pl.multiple_of(starts[s], q), wb)]
                for s in range(_S)]
        return jnp.concatenate(rows, axis=0)

    def row_dp(i, pvp, qchars, i0):
        """One stacked DP row group.  pvp: [S, wb + shift headroom] of
        D[i-1][s_{i-1} + c]; qchars: [S, _CKPT] of this block's query
        chars.  Returns (row_u [S, wb], dirs_row [S, wb])."""
        sq_i = [sqq(s, i) for s in range(_S)]
        s_i = stackv([x * q for x in sq_i])
        dq = stackv([sq_i[s] - sqq(s, i - 1) for s in range(_S)])
        pu = pvp[:, 0:wb]
        for mm in range(1, _N_SHIFT):
            pu = jnp.where(dq == mm, pvp[:, mm * q: mm * q + wb], pu)
        qc = jnp.sum(jnp.where(iota_c == (i - 1 - i0), qchars, 0),
                     axis=1, keepdims=True)           # [S, 1]
        tb = t_band([x * q for x in sq_i])
        j_u = s_i + cols_s
        sub_u = jnp.where(tb == qc, 0, 1)
        du = pu + sub_u
        vu = pu + 1
        t_u = jnp.minimum(jnp.pad(du, ((0, 0), (1, 0)),
                                  constant_values=big)[:, :wb], vu)
        t_u2 = jnp.where(j_u == 0, i, t_u)
        t_u2 = jnp.where(j_u > tl_col, big, t_u2)
        x = t_u2 - j_u
        sh = 1
        while sh < wb:
            x = jnp.minimum(
                x, jnp.pad(x, ((0, 0), (sh, 0)),
                           constant_values=big)[:, :wb])
            sh <<= 1
        row = jnp.minimum(x + j_u, big)
        dshift = jnp.pad(du, ((0, 0), (1, 0)),
                         constant_values=big)[:, :wb]
        dr = jnp.where(
            row == dshift, _MV_DIAG,
            jnp.where(row == vu, _MV_UP, _MV_LEFT)).astype(jnp.int32)
        dr = jnp.where(j_u == 0, _MV_UP, dr)
        return row, dr

    def pad_row(row):
        return jnp.pad(row, ((0, 0), (0, _N_SHIFT * q)),
                       constant_values=big)

    # ---- pass 1: forward scores, checkpoints every _CKPT rows -------
    def ck_save(slot, rows):
        # tiled HBM slices must be 8-row aligned AND 8 rows long --
        # exactly one _S=8 row group per checkpoint slot
        ckstage[0:_S, :] = rows
        cp = pltpu.make_async_copy(
            ckstage,
            ckpt_hbm.at[pl.ds(pl.multiple_of(ck0 + slot * 8, 8),
                              8), :],
            dsem)
        cp.start()
        cp.wait()

    def ck_load(slot):
        cp = pltpu.make_async_copy(
            ckpt_hbm.at[pl.ds(pl.multiple_of(ck0 + slot * 8, 8),
                              8), :],
            ckstage, dsem)
        cp.start()
        cp.wait()
        return ckstage[0:_S, :]

    init = jnp.where(cols_s > tl_col, big, cols_s)   # D[0][j] = j
    ck_save(0, init)
    max_ql = qls[0]
    for s in range(1, _S):
        max_ql = jnp.maximum(max_ql, qls[s])

    def qchars_blk(i0):
        # char window anchored to 128 lanes (ckrows may be 64)
        i0b = (i0 // 128) * 128
        rows = [q_ref[s, :, pl.ds(pl.multiple_of(i0b, 128), 128)]
                for s in range(_S)]
        return jnp.concatenate(rows, axis=0), i0b     # [S, 128]

    ql_col1 = stackv(qls)

    def blk_fwd(bk, pv):
        i0 = bk * ckrows
        qchars, i0b = qchars_blk(i0)

        def row_step(i, pv):
            row, _ = row_dp(i, pv, qchars, i0b)
            # a pair whose query ended keeps its final row frozen so
            # the end score survives to the loop exit
            row = jnp.where(ql_col1 < i, pv[:, 0:wb], row)
            return pad_row(row)

        top = jnp.minimum((bk + 1) * ckrows, max_ql)
        pv = lax.fori_loop(i0 + 1, top + 1, row_step, pv)

        @pl.when(top == (bk + 1) * ckrows)
        def _():
            ck_save(bk + 1, pv[:, 0:wb])
        return pv

    nblk = (max_ql + ckrows - 1) // ckrows
    pv = lax.fori_loop(0, nblk, blk_fwd, pad_row(init))

    # NOTE on the freeze: once i passes ql_s, pair s's row stops
    # updating, so its band start must also stop moving -- sqq(s, i)
    # with i > ql_s would drift.  The freeze keeps the row contents of
    # row ql_s, whose band start is sqq(s, ql_s); the end-score read
    # below uses exactly that start, so they agree.
    for s in range(_S):
        c_end = tls[s] - sqq(s, qls[s]) * q
        dval = jnp.sum(jnp.where((rows_s == s) &
                                 (cols_s == jnp.clip(c_end, 0,
                                                     wb - 1)),
                                 pv[:, 0:wb], 0))
        dval = jnp.where((c_end < 0) | (c_end >= wb), big, dval)
        dist_ref[s, 0:1, 0:1] = jnp.full((1, 1), dval, jnp.int32)

    # ---- pass 2: checkpointed traceback, all pairs per block --------
    for s in range(_S):
        tape_ref[s, :, :] = jnp.zeros((tape_rows, 128), jnp.int32)
    # regs per pair s at base s*8: 0 word, 1 word count, 2 bit count,
    # 3 i, 4 j
    for s in range(_S):
        regs_s[s * 8 + 0] = jnp.int32(0)
        regs_s[s * 8 + 1] = jnp.int32(0)
        regs_s[s * 8 + 2] = jnp.int32(0)
        regs_s[s * 8 + 3] = qls[s]
        regs_s[s * 8 + 4] = tls[s]

    def put_word(s, w):
        """Append one finished 16-move word: accumulate into the
        pair's 128-lane row register and flush whole rows -- the tape
        output packs 128 words per sublane row, so nothing is stored
        through the ~800ns dynamic-scalar path and the block is not
        lane-padded 128x in VMEM."""
        wcnt = regs_s[s * 8 + 1]
        lane = wcnt % 128
        taperow[s:s + 1, :] = jnp.where(iota_c == lane, w,
                                        taperow[s:s + 1, :])

        @pl.when(lane == 127)
        def _():
            tape_ref[s, pl.ds(wcnt // 128, 1), :] = taperow[s:s + 1, :]
        regs_s[s * 8 + 1] = wcnt + 1

    def emit(s, mv):
        w = regs_s[s * 8] | (mv << (regs_s[s * 8 + 2] * 2))
        nb = regs_s[s * 8 + 2] + 1
        full = nb == 16

        @pl.when(full)
        def _():
            put_word(s, w)
            regs_s[s * 8] = jnp.int32(0)
            regs_s[s * 8 + 2] = jnp.int32(0)

        @pl.when(jnp.logical_not(full))
        def _():
            regs_s[s * 8] = w
            regs_s[s * 8 + 2] = nb

    def blk_bwd(bkr, _):
        bk = nblk - 1 - bkr
        i0 = bk * ckrows
        any_here = regs_s[3] > i0
        for s in range(1, _S):
            any_here = any_here | (regs_s[s * 8 + 3] > i0)

        @pl.when(any_here)
        def _():
            # rebuild this block's direction rows from its checkpoint
            qchars, i0b = qchars_blk(i0)

            def row_step(i, pv):
                row, dr = row_dp(i, pv, qchars, i0b)
                dirs[pl.ds(pl.multiple_of((i - 1 - i0) * 8, 8),
                           _S), :] = dr
                row = jnp.where(ql_col1 < i, pv[:, 0:wb], row)
                return pad_row(row)

            top = jnp.minimum(i0 + ckrows, max_ql)
            pv0 = pad_row(ck_load(bk))
            lax.fori_loop(i0 + 1, top + 1, row_step, pv0)

            for s in range(_S):
                def w_cond(c):
                    i, j = c
                    return (i > i0) | ((i0 == 0) &
                                       ((i > 0) | (j > 0)))

                def w_body(c, s=s):
                    i, j = c

                    @pl.when(i == 0)
                    def _():
                        emit(s, jnp.int32(_MV_LEFT))

                    @pl.when(i > 0)
                    def _():
                        s_i = sqq(s, i) * q
                        cc = jnp.clip(j - s_i, 0, wb - 1)
                        drow = dirs[pl.ds((i - 1 - i0) * 8 + s,
                                          1), :]
                        mv = jnp.sum(jnp.where(cols == cc, drow, 0))
                        mv = jnp.where(j <= 0, _MV_UP, mv)
                        emit(s, mv)
                        regs_s[s * 8 + 3] = jnp.where(mv != _MV_LEFT,
                                                      i - 1, i)
                        regs_s[s * 8 + 4] = jnp.where(mv != _MV_UP,
                                                      j - 1, j)
                    ni = jnp.where(i == 0, i, regs_s[s * 8 + 3])
                    nj = jnp.where(i == 0, j - 1, regs_s[s * 8 + 4])
                    regs_s[s * 8 + 3] = ni
                    regs_s[s * 8 + 4] = nj
                    return ni, nj

                ii, jj = lax.while_loop(
                    w_cond, w_body,
                    (regs_s[s * 8 + 3], regs_s[s * 8 + 4]))
                regs_s[s * 8 + 3] = ii
                regs_s[s * 8 + 4] = jj
        return 0

    lax.fori_loop(0, nblk, blk_bwd, 0)
    for s in range(_S):
        @pl.when(regs_s[s * 8 + 2] > 0)
        def _(s=s):
            put_word(s, regs_s[s * 8])

        # flush the partial final row (garbage tail lanes are beyond
        # the move count the host slices by)
        @pl.when(regs_s[s * 8 + 1] % 128 > 0)
        def _(s=s):
            tape_ref[s, pl.ds(regs_s[s * 8 + 1] // 128, 1), :] = \
                taperow[s:s + 1, :]
        dist_ref[s, 1:2, 0:1] = jnp.full(
            (1, 1),
            regs_s[s * 8 + 1] * 16 - jnp.where(
                regs_s[s * 8 + 2] > 0, 16 - regs_s[s * 8 + 2], 0),
            jnp.int32)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _align(q, t, ql, tl, ctr, lq: int, lt: int, wb: int,
           interpret: bool = False):
    b = q.shape[0]
    tape_w = (lq + lt) // 16 + 1
    tape_rows = (tape_w + 127) // 128
    q_i = q.astype(jnp.int32)[:, None, :]
    t_i = jnp.pad(t.astype(jnp.int32), ((0, 0), (0, wb + 128)),
                  constant_values=-1)[:, None, :]
    ckrows = _ckrows(wb)
    kern = functools.partial(_kernel, lq=lq, lt=lt, wb=wb,
                             ckrows=ckrows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b // _S,),
        in_specs=[
            pl.BlockSpec((_S, 1, lq), lambda i, *_: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_S, 1, lt + wb + 128),
                         lambda i, *_: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((_S, tape_rows, 128), lambda i, *_: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_S, 8, 1), lambda i, *_: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),      # ckpt HBM buffer
        ),
        scratch_shapes=[
            pltpu.VMEM((8, wb), jnp.int32),                    # stage
            pltpu.VMEM((ckrows * 8, wb), jnp.int32),           # dirs
            pltpu.VMEM((8, 128), jnp.int32),                   # taperow
            pltpu.SemaphoreType.DMA(()),
            pltpu.SMEM((8 * _S,), jnp.int32),                  # regs
        ],
    )
    nck8 = (lq // ckrows + 1) * 8
    tape, meta, _ = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((b, tape_rows, 128),
                                        jnp.int32),
                   jax.ShapeDtypeStruct((b, 8, 1), jnp.int32),
                   jax.ShapeDtypeStruct((b // _S * nck8, wb),
                                        jnp.int32)),
        interpret=interpret,
    )(ql, tl, ctr.reshape(-1), q_i, t_i)
    return tape, meta


def per_pair_bytes(bd: int, wb: int) -> int:
    """Device bytes one queued pair costs at band ``wb``: the
    checkpoint HBM region plus q/t/tape buffers (shared by the
    dispatch chunking and the shape-prediction prewarm; within 5% of
    the v5e compiler's memory_analysis at wb 2048-8192)."""
    return (bd // _ckrows(wb) + 1) * wb * 4 + 6 * bd


# HBM one device gives the align stage's dispatches in flight
# (pipeline_depth() of them; RACON_TPU_ALIGN_BUDGET overrides), and
# the most pairs one dispatch carries
ALIGN_BUDGET = 4 << 30
MAX_BATCH_PAIRS = 1024


def align_budget() -> int:
    try:
        return int(os.environ.get("RACON_TPU_ALIGN_BUDGET",
                                  ALIGN_BUDGET))
    except ValueError:
        return ALIGN_BUDGET


def chunk_pairs(per_pair: int, n_dev: int = 1) -> int:
    """Pairs per align dispatch at ``per_pair`` device bytes: the
    largest power of two per device whose chunks, pipeline_depth() of
    them in flight, fit align_budget(); at least one 8-pair program
    per device, at most MAX_BATCH_PAIRS in all."""
    per_chunk = align_budget() // pipeline_depth()
    n = _S
    while 2 * n * per_pair <= per_chunk \
            and 2 * n * n_dev <= MAX_BATCH_PAIRS:
        n *= 2
    return n * n_dev


def pipeline_depth() -> int:
    """In-flight chunks per device dispatch loop (RACON_TPU_PIPE_DEPTH,
    clamped to [1, 4]).  Depth 2 is the classic double buffer: chunk
    k+1 is packed on the host and enqueued while k executes and k-1's
    tapes decode; deeper keeps more chunks in flight at proportionally
    smaller per-chunk memory budgets (callers divide their HBM chunk
    cap by this depth)."""
    try:
        d = int(os.environ.get("RACON_TPU_PIPE_DEPTH", "2"))
    except ValueError:
        d = 2
    return max(1, min(d, 4))


def run_pipelined(chunks, dispatch, consume, depth: int = None) -> None:
    """Drive ``dispatch(chunk) -> collect`` over ``chunks`` keeping up
    to ``depth`` dispatches in flight, consuming strictly in FIFO
    order (``consume(chunk, collect)``).  JAX dispatch is async, so
    the host packs and enqueues chunk k+1 while the device still
    executes chunk k -- the shared loop body of the WFA rung, the
    banded rung and the POA megabatch dispatchers."""
    if depth is None:
        depth = pipeline_depth()
    from collections import deque

    inflight = deque()
    for sub in chunks:
        inflight.append((sub, dispatch(sub)))
        if len(inflight) >= max(1, depth):
            sub0, coll = inflight.popleft()
            consume(sub0, coll)
    while inflight:
        sub0, coll = inflight.popleft()
        consume(sub0, coll)


def pad_pairs(n: int, n_dev: int = 1, per_pair: int = 0) -> int:
    """Batch padding rule: power of two (floor 32), a multiple of the
    stacking factor and of the mesh size.  The floor keeps the
    compiled-variant set small enough for the prebuild manifest to
    cover it: a final-rung straggler batch of 8 pairs would otherwise
    mint its own kernel variant whose first-contact compile costs far
    more than 24 empty lanes' compute (the row loops follow real
    lengths).  Empty lanes still cost their bytes, so with the pair's
    device bytes ``per_pair`` given the floor never pads past
    ``chunk_pairs`` (one WFA pair at lq=16384, emax=2048 holds
    ~0.25 GB)."""
    from racon_tpu.utils.tuning import pow2_at_least

    floor = min(32, chunk_pairs(per_pair, n_dev)) if per_pair else 32
    n_pad = pow2_at_least(max(n, floor), _S)
    return n_pad + (-n_pad) % (_S * n_dev)


def prewarm(n: int, lq: int, lt: int, wb: int, mesh=None) -> None:
    """Populate the jit dispatch cache for one (batch, dims, band)
    variant with an all-empty batch through THE SAME entry production
    dispatch uses (sharded when the mesh has more than one device);
    run from a background thread so later band rungs are already
    traced+compiled when the first rung finishes."""
    from racon_tpu.parallel.mesh_utils import interpret_mode

    n_dev = len(mesh.devices) if mesh is not None else 1
    if n_dev > 1:
        interp = interpret_mode()
        q = jnp.zeros((n, lq), jnp.uint8)
        t = jnp.zeros((n, lt), jnp.uint8)
        zl = jnp.zeros((n,), jnp.int32)
        zc = jnp.zeros((n, _n_ctr(lq)), jnp.int32)
        out = _align_sharded(q, t, zl, zl, zc, mesh=mesh, lq=lq,
                             lt=lt, wb=wb, interpret=interp)
        jax.block_until_ready(out)
    else:
        # route through align_batch so the AOT-shelf callable the
        # production dispatch will use is the one warmed here
        align_batch([b""] * n, [b""] * n, lq, lt, wb, mesh=None)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "lq", "lt", "wb",
                                    "interpret"))
def _align_sharded(q, t, ql, tl, ctr, *, mesh, lq: int, lt: int,
                   wb: int, interpret: bool):
    """The stacked kernel sharded over the mesh batch axis (one grid
    of programs per device, no collectives — the analog of the
    reference's per-device aligner queues, cudapolisher.cpp:170-188)."""
    from racon_tpu.parallel.mesh_utils import shard_batch_map

    def shard_fn(q, t, ql, tl, ctr):
        return _align(q, t, ql, tl, ctr, lq, lt, wb, interpret)

    return shard_batch_map(shard_fn, mesh, 5, 2)(q, t, ql, tl, ctr)


def align_dispatch(queries, targets, lq: int, lt: int, wb: int,
                   mesh=None, centers=None):
    """Enqueue one aligner batch and return a zero-arg collect
    closure producing (moves, lens, dists) -- the async half of
    ``align_batch``.  A caller can dispatch chunk k+1 (and run host
    decode for chunk k) while chunk k computes, hiding transfers and
    host work behind device time (the POA megabatch pipeline's
    analog, racon_tpu/tpu/polisher.py).

    ``centers`` optionally carries one knot array per pair
    (estimate_center_knots) for band re-centering; None falls back to
    the proportional diagonal for every pair."""
    from racon_tpu.tpu.aligner import encode_batch, _QPAD, _TPAD

    import threading

    n_real = len(queries)
    n_dev = len(mesh.devices) if mesh is not None else 1
    # pad the pair count to a power of two so grid sizes (and thus
    # compiled variants) stay bucketed; empty pairs cost ~nothing
    n_pad = pad_pairs(n_real, n_dev, per_pair_bytes(max(lq, lt), wb))
    queries = list(queries) + [b""] * (n_pad - n_real)
    targets = list(targets) + [b""] * (n_pad - n_real)
    q = encode_batch(queries, lq, _QPAD)
    t = encode_batch(targets, lt, _TPAD)
    ql = np.array([len(s) for s in queries], np.int32)
    tl = np.array([len(s) for s in targets], np.int32)
    ctr = np.zeros((n_pad, _n_ctr(lq)), np.int32)
    for i in range(n_pad):
        if centers is not None and i < n_real \
                and centers[i] is not None:
            ctr[i] = centers[i]
        else:
            ctr[i] = proportional_knots(int(ql[i]), int(tl[i]), lq)
    from racon_tpu.parallel.mesh_utils import interpret_mode

    interp = interpret_mode()
    t_disp = _mono()
    if n_dev > 1:
        tape, meta = _align_sharded(q, t, ql, tl, ctr, mesh=mesh,
                                    lq=lq, lt=lt, wb=wb,
                                    interpret=interp)
    else:
        from racon_tpu.utils import aot_shelf

        def build(qq, tt, qql, ttl, cc):
            return _align(qq, tt, qql, ttl, cc, lq, lt, wb, interp)

        tape, meta = aot_shelf.call(
            ("align", n_pad, lq, lt, wb, interp), __file__, build,
            (q, t, ql, tl, ctr))
    tape.copy_to_host_async()
    meta.copy_to_host_async()

    # host-independent per-dispatch device time: the watcher blocks
    # on the outputs from dispatch-enqueue on, so host work between
    # dispatch and collect (decoding the previous chunk under the
    # two-deep pipeline) never inflates the span -- the bench's
    # align_device_s (VERDICT r5 #8)
    span = {}

    def _watch():
        try:
            jax.block_until_ready((tape, meta))
            t_end = _mono()
            span["s"] = t_end - t_disp
            # device-lane trace span: dispatch-enqueue -> outputs
            # ready, free of host work between dispatch and collect
            obs_trace.TRACER.add_span(
                f"device.align_band{wb}", t_disp, t_end, cat="device",
                lane="device", args={"n": n_real})
            obs_devutil.DEVICE_UTIL.record("align_band", t_disp, t_end)
            # decision-plane exemplar (r16): the pure device interval
            # for this dispatch, free of host packing/decode time
            _decision.DECISIONS.record(
                "align_device", engine="band", rung=int(wb),
                n=int(n_real), device_s=round(t_end - t_disp, 6))
        except Exception:
            pass  # dispatch errors surface at collect()

    watcher = threading.Thread(target=_watch, daemon=True,
                               name="racon-align-devtime")
    watcher.start()

    def collect():
        tp = np.asarray(tape)[:n_real].reshape(n_real, -1) \
            .astype(np.uint32)
        mt = np.asarray(meta)[:n_real, :, 0]
        watcher.join()
        n = tp.shape[1] * 16
        moves = np.zeros((tp.shape[0], n), np.uint8)
        for sh in range(16):
            moves[:, sh::16] = (tp >> (2 * sh)) & 3
        return moves, mt[:, 1], mt[:, 0]

    collect.device_s = lambda: span.get("s", 0.0)
    return collect


def align_batch(queries, targets, lq: int, lt: int, wb: int,
                mesh=None, centers=None):
    """Align padded pair batches; returns (moves, lens, dists).

    moves: [B, n] uint8 of 2-bit codes in traceback (reversed) order,
    lens: [B] number of valid moves, dists: [B] band edit distance
    (_BIG when the endpoint fell outside the band)."""
    return align_dispatch(queries, targets, lq, lt, wb, mesh=mesh,
                          centers=centers)()


# ---------------------------------------------------------------------------
# Device WFA (wavefront) kernel: align cost scales with DISTANCE, not band^2
# ---------------------------------------------------------------------------
#
# The banded kernel above does wb x lq work per pair no matter how
# similar the sequences are, serialized by its per-row prefix-min
# chain; the CPU engine (native/align.cpp) is the O(N + D^2)
# unit-cost wavefront algorithm, which is why divergence used to hand
# the align stage back to the host.  This kernel is the device-shaped
# wavefront: wavefront e has a statically bounded diagonal extent
# (lane c <-> diagonal d = c - emax, 8 pairs stacked on sublanes), so
# every e-step is a fixed-width vector body and the serial chain is
# ~DISTANCE steps long instead of lq rows.  The furthest-reaching
# extension is a vectorized LCP over precomputed match-bit words
# (one XLA elementwise+gather pre-pass builds, per diagonal, the
# 32-chars-per-int32 match bits; the kernel slides via a
# trailing-ones popcount on each lane's cached word and DMA-refills
# exhausted words from an 8-row window anchored at the neediest
# lane).  The wavefront history lands in HBM; an in-kernel lockstep
# traceback re-derives each step's predecessor with EXACTLY the
# native engine's candidate and preference rules, so the emitted
# (slide, op) tape decodes to byte-identical CIGARs with the CPU WFA
# -- and the compact tape (<= emax+2 int32 entries per pair) is all
# that travels device->host.
#
# Failure contract: a pair whose distance exceeds ``emax`` (or whose
# length difference already does) reports _BIG and keeps no tape; the
# polisher escalates it to the re-centered banded rung (reject code
# "wfa<emax>" in align_retry_counts).

_WFA_NEG = -(1 << 20)        # inactive-diagonal sentinel
_WFA_NEG_H = -(1 << 19)      # activity threshold (> any real deficit)
_W_SUB, _W_INS, _W_DEL = 1, 2, 3   # tape op codes (0 = final slide)


def _wfa_wd(emax: int) -> int:
    """Diagonal extent (lanes): covers d in [-emax, emax], 128-padded."""
    return ((2 * emax + 2) + 127) // 128 * 128


def _wfa_nwords(lq: int) -> int:
    """Match-bit words per diagonal (8-row aligned for DMA windows)."""
    return ((lq // 32 + 2) + 7) // 8 * 8


def _wfa_tape_rows(emax: int) -> int:
    return (emax + 2 + 127) // 128


def wfa_available() -> bool:
    """Device WFA rung gate: RACON_TPU_WFA=0 keeps the banded-only
    ladder (the pre-WFA behavior; the TPU CI golden configs pin this
    until their committed bytes are regenerated)."""
    if os.environ.get("RACON_TPU_WFA", "1") == "0":
        return False
    return available()


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _wfa_match_words(q, t, lq: int, emax: int, wd: int):
    """Per-diagonal match bits, packed 32 query rows per int32.

    Word r of diagonal c holds bit k = (q[i] == t[i + c - emax]) for
    i = 32r + k; out-of-range positions compare pads (q pad 5, t pad
    6, shift sentinel 7) and are always 0, so the kernel's slide
    stops at sequence boundaries with no extra masking.  One fused
    elementwise pass at memory bandwidth -- the O(wd x lq) element
    count looks like the banded DP's, but these are independent byte
    compares, not a serialized scoring recurrence.  Returns
    [B * nwords, wd] (2-D so the kernel's refill DMA windows are
    plain 8-row slices)."""
    b = q.shape[0]
    nwords = _wfa_nwords(lq)
    li = nwords * 32
    from racon_tpu.tpu.aligner import _QPAD

    qq = jnp.pad(q, ((0, 0), (0, li - lq)), constant_values=_QPAD)
    tp = jnp.full((b, li + wd), 7, jnp.uint8)
    tp = lax.dynamic_update_slice(tp, t, (0, emax))

    def one_diag(c):
        return lax.dynamic_slice_in_dim(tp, c, li, axis=1)

    tsh = jax.vmap(one_diag, out_axes=1)(jnp.arange(wd))  # [b, wd, li]
    eqw = (qq[:, None, :] == tsh).reshape(b, wd, nwords, 32)
    word = jnp.zeros((b, wd, nwords), jnp.uint32)
    for k in range(32):
        word = word | (eqw[..., k].astype(jnp.uint32)
                       << np.uint32(k))
    word = lax.bitcast_convert_type(word, jnp.int32)
    return jnp.transpose(word, (0, 2, 1)).reshape(b * nwords, wd)


def _wfa_kernel(ql_ref, tl_ref, mw_hbm, tape_ref, meta_ref, hist_hbm,
                F, W, BW, win, taperow, dsems, hsem, regs_s, *,
                lq: int, emax: int, wd: int, nwords: int):
    g0 = pl.program_id(0) * _S
    h0 = pl.program_id(0) * (emax + 1) * 8
    big = jnp.int32(_BIG)
    neg = jnp.int32(_WFA_NEG)
    negh = jnp.int32(_WFA_NEG_H)
    tape_rows = _wfa_tape_rows(emax)
    cols_s = lax.broadcasted_iota(jnp.int32, (_S, wd), 1)
    rows_s = lax.broadcasted_iota(jnp.int32, (_S, wd), 0)
    wrow8 = lax.broadcasted_iota(jnp.int32, (8, wd), 0)
    riota = lax.broadcasted_iota(jnp.int32, (_S, 1), 0)
    iota_c = lax.broadcasted_iota(jnp.int32, (1, 128), 1)

    qls = [ql_ref[g0 + s] for s in range(_S)]
    tls = [tl_ref[g0 + s] for s in range(_S)]
    valids = [(qls[s] > 0) & (tls[s] > 0)
              & (jnp.abs(tls[s] - qls[s]) <= emax)
              for s in range(_S)]

    def stackv(vals, dtype=jnp.int32):
        out = jnp.full((_S, 1), 0, dtype)
        for s, v in enumerate(vals):
            out = jnp.where(riota == s, jnp.asarray(v, dtype), out)
        return out

    ql_col = stackv(qls)
    tl_col = stackv(tls)
    valid_col = stackv([jnp.where(v, 1, 0) for v in valids]) > 0
    fin_col = tl_col - ql_col + emax       # lane of the final diagonal
    d_col = cols_s - emax                  # each lane's diagonal

    # regs per pair s at base s*8: 0 dist (-1 pending / e / _BIG
    # failed), 1 cur_i, 2 cur_d, 3 tape entry count
    for s in range(_S):
        regs_s[s * 8 + 0] = jnp.where(valids[s], -1, big)
        regs_s[s * 8 + 3] = jnp.int32(0)

    def dist_done_col():
        dist_col = stackv([regs_s[s * 8] for s in range(_S)])
        return dist_col, dist_col != -1

    def extend():
        """LCP extension to the furthest-reaching points: slide every
        lane along its cached match word (trailing-ones popcount),
        refilling exhausted words from an 8-row DMA window anchored
        at each pair's neediest lane.  Loops until no active lane
        awaits a word; each round serves at least the minimum-index
        needy lane, so it terminates."""
        _, done_col = dist_done_col()

        def body(_):
            Fv = F[0:_S, :]
            active = (Fv > negh) & ~done_col & (Fv < ql_col)
            needy = active & ((Fv >> 5) != BW[0:_S, :])
            widx = jnp.where(needy, Fv >> 5, jnp.int32(1 << 24))
            cps, rlos = [], []
            for s in range(_S):
                rlo = jnp.min(jnp.where(rows_s == s, widx,
                                        jnp.int32(1 << 24)))
                rlo8 = jnp.clip((rlo >> 3) << 3, 0, nwords - 8)
                cp = pltpu.make_async_copy(
                    mw_hbm.at[pl.ds(pl.multiple_of(
                        (g0 + s) * nwords + rlo8, 8), 8), :],
                    win.at[pl.ds(pl.multiple_of(s * 8, 8), 8), :],
                    dsems.at[s])
                cp.start()
                cps.append(cp)
                rlos.append(rlo8)
            for cp in cps:
                cp.wait()
            f5 = Fv >> 5
            for s in range(_S):
                wnd = win[s * 8:(s + 1) * 8, :]
                f5s = f5[s:s + 1, :]
                served = needy[s:s + 1, :] & (f5s >= rlos[s]) \
                    & (f5s < rlos[s] + 8)
                sel = jnp.sum(
                    jnp.where(wrow8[0:8, :] + rlos[s] == f5s, wnd, 0),
                    axis=0, keepdims=True)
                W[s:s + 1, :] = jnp.where(served, sel, W[s:s + 1, :])
                BW[s:s + 1, :] = jnp.where(served, f5s,
                                           BW[s:s + 1, :])
            have = active & ((Fv >> 5) == BW[0:_S, :])
            x = lax.shift_right_logical(W[0:_S, :], Fv & 31)
            y = ~x
            lsb = y & (-y)
            tr = lax.population_count(lsb - 1)
            Fn = jnp.where(have, Fv + tr, Fv)
            F[0:_S, :] = Fn
            needy2 = (Fn > negh) & ~done_col & (Fn < ql_col) \
                & ((Fn >> 5) != BW[0:_S, :])
            return jnp.sum(needy2.astype(jnp.int32)) > 0

        lax.while_loop(lambda c: c, body, jnp.bool_(True))

    def estep():
        """One wavefront advance: candidates exactly as the native
        wf_candidate (del keeps i from d-1; sub/ins advance i from
        d/d+1), furthest = max, boundary masks identical -- the
        wavefront VALUES must equal the CPU engine's for the
        traceback tapes to agree byte-for-byte."""
        _, done_col = dist_done_col()
        Fv = F[0:_S, :]
        nl = jnp.pad(Fv, ((0, 0), (1, 0)),
                     constant_values=_WFA_NEG)[:, :wd]
        nr = jnp.pad(Fv, ((0, 0), (0, 1)),
                     constant_values=_WFA_NEG)[:, 1:]
        vdel = jnp.where((nl > negh) & (nl + d_col <= tl_col),
                         nl, neg)
        vsub = jnp.where((Fv > negh) & (Fv + 1 <= ql_col)
                         & (Fv + 1 + d_col <= tl_col), Fv + 1, neg)
        vins = jnp.where((nr > negh) & (nr + 1 <= ql_col),
                         nr + 1, neg)
        cand = jnp.maximum(jnp.maximum(vdel, vsub), vins)
        F[0:_S, :] = jnp.where(done_col, Fv, cand)

    def hist_write(e):
        cp = pltpu.make_async_copy(
            F, hist_hbm.at[pl.ds(pl.multiple_of(h0 + e * 8, 8),
                                 8), :], hsem)
        cp.start()
        cp.wait()

    def check_done(e):
        Fv = F[0:_S, :]
        sel = jnp.max(jnp.where(cols_s == fin_col, Fv, neg),
                      axis=1, keepdims=True)
        newly = (sel >= ql_col) & valid_col
        for s in range(_S):
            ns = jnp.sum(jnp.where(riota == s,
                                   newly.astype(jnp.int32), 0)) > 0

            @pl.when(ns & (regs_s[s * 8] == -1))
            def _(s=s):
                regs_s[s * 8] = jnp.asarray(e, jnp.int32)

    # ---- forward: wavefronts until every pair finishes or e > emax
    F[0:_S, :] = jnp.where((cols_s == emax) & valid_col, 0, neg)
    W[0:_S, :] = jnp.zeros((_S, wd), jnp.int32)
    BW[0:_S, :] = jnp.full((_S, wd), -1, jnp.int32)
    for s in range(_S):
        tape_ref[s, :, :] = jnp.zeros((tape_rows, 128), jnp.int32)
    taperow[0:8, :] = jnp.zeros((8, 128), jnp.int32)
    extend()
    hist_write(0)
    check_done(0)

    def n_done():
        nd = jnp.int32(0)
        for s in range(_S):
            nd = nd + jnp.where(regs_s[s * 8] != -1, 1, 0)
        return nd

    def fbody(c):
        e, _ = c
        estep()
        extend()
        hist_write(e)
        check_done(e)
        return e + 1, n_done()

    lax.while_loop(lambda c: (c[0] <= emax) & (c[1] < _S), fbody,
                   (jnp.int32(1), n_done()))
    for s in range(_S):
        @pl.when(regs_s[s * 8] == -1)
        def _(s=s):
            regs_s[s * 8] = big                # ran past emax: reject

    # ---- traceback: lockstep walk from each pair's distance to 0,
    # re-deriving predecessors from the HBM history with the native
    # engine's preference order (ins > sub > del)
    for s in range(_S):
        regs_s[s * 8 + 1] = qls[s]
        regs_s[s * 8 + 2] = tls[s] - qls[s]
    e_top = jnp.int32(0)
    for s in range(_S):
        e_top = jnp.maximum(
            e_top, jnp.where(regs_s[s * 8] < big, regs_s[s * 8], 0))

    def put_entry(s, val):
        n = regs_s[s * 8 + 3]
        lane = n % 128
        taperow[s:s + 1, :] = jnp.where(iota_c == lane, val,
                                        taperow[s:s + 1, :])

        @pl.when(lane == 127)
        def _():
            tape_ref[s, pl.ds(n // 128, 1), :] = taperow[s:s + 1, :]
        regs_s[s * 8 + 3] = n + 1

    def tbody(e):
        cp = pltpu.make_async_copy(
            hist_hbm.at[pl.ds(pl.multiple_of(h0 + (e - 1) * 8, 8),
                              8), :], F, hsem)
        cp.start()
        cp.wait()
        prev = F[0:_S, :]
        dist_col, _ = dist_done_col()
        i_col = stackv([regs_s[s * 8 + 1] for s in range(_S)])
        dcur = stackv([regs_s[s * 8 + 2] for s in range(_S)])
        active_col = (dist_col < big) & (e <= dist_col)
        c_col = dcur + emax

        def pick(delta):
            return jnp.max(
                jnp.where(cols_s == c_col + delta, prev, neg),
                axis=1, keepdims=True)

        vm1, v0, vp1 = pick(-1), pick(0), pick(1)
        del_c = jnp.where((vm1 > negh) & (vm1 + dcur <= tl_col),
                          vm1, neg)
        sub_c = jnp.where((v0 > negh) & (v0 + 1 <= ql_col)
                          & (v0 + 1 + dcur <= tl_col), v0 + 1, neg)
        ins_c = jnp.where((vp1 > negh) & (vp1 + 1 <= ql_col),
                          vp1 + 1, neg)
        i0 = jnp.maximum(jnp.maximum(del_c, sub_c), ins_c)
        is_ins = (ins_c > negh) & (ins_c == i0)
        is_sub = ~is_ins & (sub_c > negh) & (sub_c == i0)
        entry = (i_col - i0) * 4 + jnp.where(
            is_ins, _W_INS, jnp.where(is_sub, _W_SUB, _W_DEL))
        ni = jnp.where(is_ins | is_sub, i0 - 1, i0)
        nd2 = jnp.where(is_ins, dcur + 1,
                        jnp.where(is_sub, dcur, dcur - 1))
        for s in range(_S):
            act = jnp.sum(jnp.where(
                riota == s, active_col.astype(jnp.int32), 0)) > 0

            @pl.when(act)
            def _(s=s):
                put_entry(s, jnp.sum(jnp.where(riota == s, entry,
                                               0)))
                regs_s[s * 8 + 1] = jnp.sum(
                    jnp.where(riota == s, ni, 0))
                regs_s[s * 8 + 2] = jnp.sum(
                    jnp.where(riota == s, nd2, 0))
        return e - 1

    lax.while_loop(lambda e: e > 0, tbody, e_top)
    for s in range(_S):
        @pl.when(regs_s[s * 8] < big)
        def _(s=s):
            put_entry(s, regs_s[s * 8 + 1] * 4)   # e == 0 slide

        @pl.when(regs_s[s * 8 + 3] % 128 > 0)
        def _(s=s):
            tape_ref[s, pl.ds(regs_s[s * 8 + 3] // 128, 1), :] = \
                taperow[s:s + 1, :]
        meta_ref[s, 0:1, 0:1] = jnp.full((1, 1), regs_s[s * 8],
                                         jnp.int32)
        meta_ref[s, 1:2, 0:1] = jnp.full((1, 1), regs_s[s * 8 + 3],
                                         jnp.int32)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _wfa_call(q, t, ql, tl, lq: int, emax: int,
              interpret: bool = False):
    b = q.shape[0]
    wd = _wfa_wd(emax)
    nwords = _wfa_nwords(lq)
    mw = _wfa_match_words(q, t, lq, emax, wd)
    tape_rows = _wfa_tape_rows(emax)
    kern = functools.partial(_wfa_kernel, lq=lq, emax=emax, wd=wd,
                             nwords=nwords)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b // _S,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],   # match words
        out_specs=(
            pl.BlockSpec((_S, tape_rows, 128), lambda i, *_: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_S, 8, 1), lambda i, *_: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),          # history HBM
        ),
        scratch_shapes=[
            pltpu.VMEM((_S, wd), jnp.int32),            # wavefront F
            pltpu.VMEM((_S, wd), jnp.int32),            # cached words
            pltpu.VMEM((_S, wd), jnp.int32),            # word indices
            pltpu.VMEM((_S * 8, wd), jnp.int32),        # refill window
            pltpu.VMEM((8, 128), jnp.int32),            # taperow
            pltpu.SemaphoreType.DMA((_S,)),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SMEM((8 * _S,), jnp.int32),
        ],
    )
    tape, meta, _ = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((b, tape_rows, 128),
                                        jnp.int32),
                   jax.ShapeDtypeStruct((b, 8, 1), jnp.int32),
                   jax.ShapeDtypeStruct((b // _S * (emax + 1) * 8,
                                         wd), jnp.int32)),
        interpret=interpret,
    )(ql, tl, mw)
    return tape, meta


@functools.partial(jax.jit,
                   static_argnames=("mesh", "lq", "emax", "interpret"))
def _wfa_sharded(q, t, ql, tl, *, mesh, lq: int, emax: int,
                 interpret: bool):
    from racon_tpu.parallel.mesh_utils import shard_batch_map

    def shard_fn(q, t, ql, tl):
        return _wfa_call(q, t, ql, tl, lq, emax, interpret)

    return shard_batch_map(shard_fn, mesh, 4, 2)(q, t, ql, tl)


def wfa_per_pair_bytes(lq: int, emax: int) -> int:
    """Device bytes one queued pair costs at max e-step ``emax``: the
    HBM wavefront history ((emax+1) x wd int32 rows), the packed
    match words, the q/t/tape buffers, and the match-word pre-pass's
    [wd, li] byte intermediates (shifted target, compare mask), which
    dominate.  The v5e compiler's memory_analysis measured 2.6-3.0
    bytes per wd x li cell across lq 2048-16384, emax 512-2048 and
    8-256 pairs (40 MB/pair at lq=10112, emax=512; 248 MB at 16384,
    2048); 3.25 bounds it."""
    wd = _wfa_wd(emax)
    nwords = _wfa_nwords(lq)
    return ((emax + 1) * wd * 4 + nwords * wd * 4 + 8 * lq
            + 13 * wd * nwords * 32 // 4)


def wfa_dispatch(queries, targets, lq: int, emax: int, mesh=None):
    """Enqueue one WFA batch; returns a zero-arg collect closure
    producing (tapes, n_entries, dists) -- dists are EXACT edit
    distances (<= emax) or _BIG for rejected pairs.  Same two-deep
    pipeline contract as ``align_dispatch``."""
    from racon_tpu.tpu.aligner import encode_batch, _QPAD, _TPAD

    import threading

    n_real = len(queries)
    n_dev = len(mesh.devices) if mesh is not None else 1
    n_pad = pad_pairs(n_real, n_dev, wfa_per_pair_bytes(lq, emax))
    queries = list(queries) + [b""] * (n_pad - n_real)
    targets = list(targets) + [b""] * (n_pad - n_real)
    q = encode_batch(queries, lq, _QPAD)
    t = encode_batch(targets, lq, _TPAD)
    ql = np.array([len(s) for s in queries], np.int32)
    tl = np.array([len(s) for s in targets], np.int32)
    from racon_tpu.parallel.mesh_utils import interpret_mode

    interp = interpret_mode()
    t_disp = _mono()
    if n_dev > 1:
        tape, meta = _wfa_sharded(q, t, ql, tl, mesh=mesh, lq=lq,
                                  emax=emax, interpret=interp)
    else:
        from racon_tpu.utils import aot_shelf

        def build(qq, tt, qql, ttl):
            return _wfa_call(qq, tt, qql, ttl, lq, emax, interp)

        tape, meta = aot_shelf.call(
            ("align_wfa", n_pad, lq, emax, interp), __file__, build,
            (q, t, ql, tl))
    tape.copy_to_host_async()
    meta.copy_to_host_async()
    span = {}

    def _watch():
        try:
            jax.block_until_ready((tape, meta))
            t_end = _mono()
            span["s"] = t_end - t_disp
            obs_trace.TRACER.add_span(
                f"device.align_wfa{emax}", t_disp, t_end,
                cat="device", lane="device", args={"n": n_real})
            obs_devutil.DEVICE_UTIL.record("align_wfa", t_disp, t_end)
            _decision.DECISIONS.record(
                "align_device", engine="wfa", rung=int(emax),
                n=int(n_real), device_s=round(t_end - t_disp, 6))
        except Exception:
            pass  # dispatch errors surface at collect()

    watcher = threading.Thread(target=_watch, daemon=True,
                               name="racon-wfa-devtime")
    watcher.start()

    def collect():
        tp = np.asarray(tape)[:n_real].reshape(n_real, -1) \
            .astype(np.int64)
        mt = np.asarray(meta)[:n_real, :, 0]
        watcher.join()
        return tp, mt[:, 1], mt[:, 0]

    collect.device_s = lambda: span.get("s", 0.0)
    return collect


def wfa_batch(queries, targets, lq: int, emax: int, mesh=None):
    """Synchronous wrapper over ``wfa_dispatch``."""
    return wfa_dispatch(queries, targets, lq, emax, mesh=mesh)()


def wfa_prewarm(n: int, lq: int, emax: int, mesh=None) -> None:
    """Populate the jit/AOT caches for one WFA variant through the
    same entry production dispatch uses (see ``prewarm``)."""
    from racon_tpu.parallel.mesh_utils import interpret_mode

    n_dev = len(mesh.devices) if mesh is not None else 1
    if n_dev > 1:
        interp = interpret_mode()
        q = jnp.zeros((n, lq), jnp.uint8)
        t = jnp.zeros((n, lq), jnp.uint8)
        zl = jnp.zeros((n,), jnp.int32)
        out = _wfa_sharded(q, t, zl, zl, mesh=mesh, lq=lq, emax=emax,
                           interpret=interp)
        jax.block_until_ready(out)
    else:
        wfa_batch([b""] * n, [b""] * n, lq, emax, mesh=None)


def wfa_tape_to_ops(tape_row: np.ndarray, n_entries: int):
    """Decode one WFA (slide, op) tape row into the aligner op
    alphabet, reversed (traceback) order like ``moves_to_ops``.  Each
    entry expands to ``slide`` exact matches followed by its op; sub
    steps are always true mismatches (the slide is maximal), so =/X
    needs no sequence re-compare."""
    from racon_tpu.tpu import aligner as al

    ent = tape_row[:n_entries]
    slides = ent >> 2
    opc = ent & 3
    counts = slides + (opc != 0)
    out = np.full(int(counts.sum()), al.OP_EQ, np.uint8)
    ends = np.cumsum(counts)
    has = opc != 0
    opmap = np.array([al.OP_EQ, al.OP_X, al.OP_I, al.OP_D], np.uint8)
    out[(ends - 1)[has]] = opmap[opc[has]]
    return out


def moves_to_ops(moves_row, length, query: bytes, target: bytes):
    """Decode one reversed 2-bit move row into the aligner op alphabet
    (=/X/I/D codes from racon_tpu.tpu.aligner), vectorised."""
    from racon_tpu.tpu import aligner as al

    mv = moves_row[:length][::-1]                  # forward order
    di = (mv != _MV_LEFT).astype(np.int64)
    dj = (mv != _MV_UP).astype(np.int64)
    i_idx = np.cumsum(di) - 1                      # query index used
    j_idx = np.cumsum(dj) - 1
    qa = np.frombuffer(query, np.uint8)
    ta = np.frombuffer(target, np.uint8)
    eq = np.zeros(len(mv), bool)
    m = mv == _MV_DIAG
    eq[m] = qa[i_idx[m]] == ta[j_idx[m]]
    ops = np.where(m, np.where(eq, al.OP_EQ, al.OP_X),
                   np.where(mv == _MV_UP, al.OP_I, al.OP_D))
    return ops.astype(np.uint8)[::-1]              # reversed, like scan
