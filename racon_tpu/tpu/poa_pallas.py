"""Full on-device batched POA: the flagship Pallas TPU kernel.

One grid program runs the ENTIRE partial-order-alignment consensus --
graph construction, per-layer banded DP, traceback, graph merge,
heaviest-bundle consensus, TGS trim -- for a GROUP of S windows
(``pick_windows_per_program``: 4-6 at the stock w=500 caps, 1-3 at
w=1000, by window depth), with all S POA graphs resident in VMEM/SMEM.  This is the
cudapoa architecture (reference: one CUDA thread block per POA group,
src/cuda/cudabatch.cpp:52-265) mapped to the TensorCore: host
involvement is ONE upload of the layer sequences and ONE download of
the finished consensus per megabatch.

Why several windows per program?  The per-rank DP is a serial
dependency chain (pred row -> fold -> move max -> log2(wb) gap-chain
steps -> row store), and measurement shows the kernel is bound by
that chain's LATENCY, not by op count or vector width: duplicating
any individual phase inside the rank body costs ~nothing (the VLIW
scheduler hides it in the chain's stalls), while running the whole
walk twice costs the full +78%.  Another window's chain is exactly
such independent work: interleaving S windows' rank bodies in one
straight-line region lets the scheduler fill one chain's stalls with
the others' ops, targeting ~Sx per-window throughput at unchanged op
count.  S is capped by SMEM: each window's per-node scalars must
stay scalar-addressable.  The r6 diet packs them to 13 ints/node
(down from the r5 diet's 26): the ten per-node scalar arrays hold
values < 2^16, so they live as five half-width PAIRS packed two
fields per int32 (base|nseq, anch|minsucc, nxt|glast, pcnt|scnt,
gcnt|bandq), the whole pred-weight mirror spills to a VMEM row per
node (weights exceed 16 bits and their accumulate is a masked
vector add, not a chain-latency scalar read), and the consensus
score array -- the one field that genuinely needs 32 bits -- aliases
the path tape, which is dead until the consensus backtrack.  That
takes the stock w=500 shape from S=3 to S=5 and w=1000 from 1 to 2.

On top of S, the joint DP walk steps KRANK ranks of every window per
while-loop iteration (multi-rank stepping): topo runs of single-
predecessor backbone nodes -- the overwhelmingly common case -- make
almost every unrolled step productive, so the loop's per-iteration
overhead (condition fold, carry shuffle, region boundary) is paid
once per KRANK ranks and the straight-line region grows to
S x KRANK interleavable rank bodies.  Inert tail steps (a window
whose walk already ended) are free: the rank body is fully gated on
node >= 0.

Why not the lockstep host-graph design (racon_tpu/tpu/poa.py)?  The
lockstep engine pays two host<->device transfers per layer round
(~38 rounds on the reference sample workload); this kernel pays two
per megabatch.

Graph representation (per window, V node slots):

* per-node scalars in SMEM: base, anchor (backbone position), nseqs,
  list-next, aligned-group-last, topo rank (epoch-tagged), pred id
  mirror (8 slots) and pred weights;
* adjacency ids in VMEM int32 arrays: preds [V,P], succs [V,S];
  aligned groups [V,A] as base-tagged entries (sib * 256 + sib_base);
* topological order is maintained as a singly-linked list grouped by
  alignment column: new columns insert after the previous path node's
  column, new aligned members insert adjacent to their column.  Edges
  only ever point column-forward, so the list stays topologically
  valid and each layer needs one O(V) walk instead of a Kahn sort
  (spoa re-sorts per added sequence; cudapoa re-sorts on device).

The per-layer DP is the same banded graph-vs-sequence recurrence as
the scan kernels in poa.py (band quantum q = 128, pred rows fetched
from per-node VMEM rows, in-row gap chain closed with a max-plus
doubling scan), with first-slot-on-tie direction codes so tracebacks
are deterministic.  Graph-semantics parity target is the native CPU
engine (racon_tpu/native/poa_graph.hpp); like the CUDA path vs spoa,
cost-equal alignment ties may resolve differently, so consensus
equality is validated within an edit tolerance, not byte-for-byte.

Windows that overflow any cap (V nodes, P/S edges, A aligned, band
reach, path length) fail with a code and fall back to the CPU engine,
the reference's rejection contract (cudabatch.cpp:124-155 ->
cudapolisher.cpp:357-386).
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

from racon_tpu.obs import devutil as obs_devutil
from racon_tpu.obs import trace as obs_trace

# the sanctioned clock (racon_tpu/obs): the watcher span feeds only
# the trace and the device_s reporting counter, never control flow
_mono = obs_trace.now

_BIG = 1 << 28
_N_SHIFT = 4          # pred band may lag <= 3 quanta of 128

# fail codes (observability parity with the lockstep export codes)
FAIL_VCAP = 1
FAIL_EDGE = 2         # pred/succ slot overflow (pcap analog)
FAIL_KCAP = 3         # band reach: pred band lagged out of shift
                      # range, or no subset sink within band reach
FAIL_ALIGNED = 4
FAIL_PATH = 5

_NREG = 16            # regs slots per window


def available() -> bool:
    """True when the on-device POA path should be used: a real TPU
    backend, or any backend with interpret mode forced (the multichip
    dryrun and the sharding tests set RACON_TPU_PALLAS_INTERPRET=1 so
    the production dispatch path is exercised without TPU hardware)."""
    if os.environ.get("RACON_TPU_NO_PALLAS"):
        return False
    if os.environ.get("RACON_TPU_PALLAS_INTERPRET") == "1":
        return True
    # a backend that fails to start raises: it must not quietly
    # select the lockstep engine
    return jax.devices()[0].platform == "tpu"


def band_width(lp: int, banded: bool = False) -> int:
    """The on-device DP band width for layer cap ``lp``: the shared
    band policy (racon_tpu.utils.tuning.poa_band_cols -- one source
    of truth with the lockstep engine and the memory/prewarm shape
    predictions) rounded up to the 128-lane quantum and clamped to
    the padded row."""
    from racon_tpu.utils.tuning import poa_band_cols

    wb = poa_band_cols(lp, banded) or (lp + 1)   # 0 = degenerate
    return min((wb + 127) & ~127, ((lp + 127) & ~127))


def prewarm(b: int, d1: int, *, v: int, lp: int, wb: int,
            p: int = 16, s: int = 16, a: int = 8, k: int = 128,
            match: int = 5, mismatch: int = -4, gap: int = -8,
            wtype: int = 1, trim: int = 1, mesh=None) -> None:
    """Populate the jit dispatch cache for one kernel shape by running
    an inert 1-base batch (device-side zeros, no host upload) through
    THE SAME entry production dispatch uses (sharded when the mesh has
    more than one device).  Called from a background thread while the
    align stage owns the device: kernel tracing (~1 s) plus the
    persistent-cache compile load (~1.5 s) dominate cold starts when
    paid serially."""
    seqs = np.zeros((b, d1, lp), np.uint8)
    seqs[:, 0, 0] = ord("A")
    wts = np.ones((b, d1, lp), np.uint8)
    meta = np.zeros((b, d1, 8), np.int32)
    nlay = np.zeros((b,), np.int32)
    bblen = np.ones((b,), np.int32)
    poa_full_batch(seqs, wts, meta, nlay, bblen, v=v, lp=lp, d1=d1,
                   p=p, s=s, a=a, k=k, wb=wb, match=match,
                   mismatch=mismatch, gap=gap, wtype=wtype, trim=trim,
                   mesh=mesh)


def _fits_s(v: int, lp: int, d1: int, p: int, s: int, a: int,
            wb: int, s_win: int, krank: int = 1) -> bool:
    """Conservative per-program VMEM estimate and the compiler's SMEM
    count for the kernel at ``s_win`` windows per program and
    ``krank`` ranks per joint DP iteration."""
    vmem = (s_win * v * wb * 4                # packed score|code rows
            + s_win * v * (p + s) * 4         # adjacency ids (VMEM)
            + s_win * v * a * 4               # aligned groups
            + s_win * v * p * 4               # pred-weight rows (all
                                              # p slots; r6 diet moved
                                              # the 8-slot SMEM mirror
                                              # here)
            + 2 * 8 * (lp + 256) * 4          # staged chw + chars rows
            + 2 * 2 * s_win * d1 * lp * 4)    # seq/wts blocks x2 buf
    # the kernel is granted a 64M scoped-vmem limit (v5e has 128M);
    # the compiler's stack temporaries for the interleaved straight-
    # line window bodies come out of the same scope (measured r5:
    # ~3M per window body at krank=1, d1=32; each extra unrolled rank
    # body adds ~0.75M since the per-window carried state is shared
    # across the unroll) -- budget declared + temps against 44M,
    # leaving 20M slack for pipeline buffers and measurement error
    temps = s_win * ((3 << 20) + ((3 << 20) >> 2) * (krank - 1))
    return vmem + temps <= (44 << 20) and \
        _smem_bytes(v, lp, d1, s_win) <= _SMEM_BYTES - _SMEM_RESERVE


# SMEM per TensorCore as the v5e compiler counts it ("Used 1.00M of
# 1.00M smem"); the reserve covers the scalar-prefetched nlay/bblen
# vectors (2 x pow2(b) words: 8K at a 1024-window per-device
# megabatch) plus the compiler's own scalars (~1.2K measured)
_SMEM_BYTES = 1 << 20
_SMEM_RESERVE = 16 << 10


def _smem_lanes(n: int) -> int:
    """Words one SMEM row of ``n`` words occupies: rows of a 2-D or
    3-D SMEM buffer pad to 128 words (the compiler reported the
    s32[5,64,8] meta window as 320K, 2 x 5 x 64 x 128 words)."""
    return (n + 127) // 128 * 128


def _smem_bytes(v: int, lp: int, d1: int, s_win: int) -> int:
    """SMEM the kernel allocates at ``s_win`` windows per program.

    Per window after the r6 diet: FIVE packed v-sized arrays
    (base|nseq, anch|minsucc, nxt|glast, pcnt|scnt, gcnt|bandq --
    every field < 2^16; consensus cpred/order reuse the bandq/glast
    halves, consensus score aliases the 32-bit path tape), the 8-slot
    pred id mirror, the packed path and the regs.  Shared: the chw
    mirror and the consensus staging.  The pipelined meta input and
    mout output blocks are double-buffered and lane-padded: at d1=64
    the meta window alone is 64K per window.  Within 3.2K of the v5e
    compiler's count at S=5/d1=64 and S=4/d1=128."""
    per_win = (v * (5 + 8) + (v + lp) + _smem_lanes(_NREG)
               + v                                  # consensus rows
               + 2 * d1 * _smem_lanes(8)            # meta block x2
               + 2 * 8 * _smem_lanes(1))            # mout block x2
    shared = 8 * _smem_lanes(lp + 256)              # chw mirror
    return 4 * (s_win * per_win + shared)


def _forced_env_factor(name: str) -> int:
    """Parse a forced kernel-shape factor env var; None when unset.
    Malformed values fail LOUDLY naming the variable (a typo silently
    routing every window to the lockstep engine cost a round of
    confusion, ADVICE r5)."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return None
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(
            f"{name} must be a positive integer, got {raw!r}")
    if not 1 <= val <= 8:
        raise ValueError(f"{name} must be in [1, 8], got {val}")
    return val


def pick_windows_per_program(v: int, lp: int, d1: int, p: int = 16,
                             s: int = 16, a: int = 8,
                             wb: int = 256) -> int:
    """Largest windows-per-program factor the budget allows (0 = the
    shape does not fit at all and the caller must use the lockstep
    engine).  More windows per program = more independent serial DP
    chains for the VLIW scheduler to interleave (see module
    docstring); SMEM binds: at the stock w=500 caps 5 fit at d1=32
    and 4 at d1=64, at w=1000 3 at d1=32."""
    force = _forced_env_factor("RACON_TPU_POA_SWIN")
    if force is not None:
        if _fits_s(v, lp, d1, p, s, a, wb, force):
            return force
        import warnings
        warnings.warn(
            f"RACON_TPU_POA_SWIN={force} exceeds the kernel budget "
            f"for shape v={v} lp={lp} d1={d1} wb={wb}; the flagship "
            "kernel is unavailable and windows fall back to the "
            "lockstep engine", RuntimeWarning, stacklevel=2)
        return 0
    for s_win in (6, 5, 4, 3, 2, 1):
        if _fits_s(v, lp, d1, p, s, a, wb, s_win):
            return s_win
    return 0


def pick_rank_unroll(v: int, lp: int, d1: int, p: int = 16,
                     s: int = 16, a: int = 8, wb: int = 256,
                     s_win: int = 0) -> int:
    """Ranks of every window processed per joint DP iteration
    (multi-rank stepping, see module docstring).  Largest of 4/2/1
    whose compiler-temp footprint still fits next to ``s_win``
    interleaved windows; RACON_TPU_POA_KRANK forces it (budget-
    rejected forces warn and fall back to the policy pick)."""
    if not s_win:
        s_win = pick_windows_per_program(v, lp, d1, p, s, a, wb)
    if s_win <= 0:
        return 1
    force = _forced_env_factor("RACON_TPU_POA_KRANK")
    if force is not None:
        if _fits_s(v, lp, d1, p, s, a, wb, s_win, force):
            return force
        import warnings
        warnings.warn(
            f"RACON_TPU_POA_KRANK={force} exceeds the kernel budget "
            f"for shape v={v} lp={lp} d1={d1} wb={wb} at "
            f"{s_win} windows/program; using the policy pick instead",
            RuntimeWarning, stacklevel=2)
    for krank in (4, 2, 1):
        if _fits_s(v, lp, d1, p, s, a, wb, s_win, krank):
            return krank
    return 1


def fits(v: int, lp: int, d1: int, p: int, s: int, a: int,
         wb: int) -> bool:
    """True when the flagship kernel can run this shape at SOME
    windows-per-program factor.  Configurations over budget use the
    lockstep engine instead of failing to compile."""
    return pick_windows_per_program(v, lp, d1, p, s, a, wb) > 0


def padded_batch(b: int, n_dev: int, v: int, lp: int, d1: int,
                 p: int = 16, s: int = 16, a: int = 8,
                 wb: int = 256) -> int:
    """The batch size dispatch will actually run for a caller-side
    batch of ``b``: rounded up to a multiple of the windows-per-program
    factor times the device count.  Prewarm/prebuild paths must
    predict THIS number or they compile a variant production never
    uses (and the AOT-shelf key never matches)."""
    s_win = max(1, pick_windows_per_program(v, lp, d1, p, s, a, wb))
    mult = s_win * max(1, n_dev)
    return b + (-b) % mult


# packed SMEM pairs (r6 diet): each (v,) int32 array holds TWO
# 16-bit fields, lo | hi << 16 (every field's range is < 2^16):
#   bnsq: base | nseq        anms: anch | minsucc (0xFFFF = inf)
#   nxgl: nxt+1 | glast      pcsc: pcnt | scnt
#   gcbq: gcnt | bandq       (bandq packs (d << 8) | band quantum;
#                             0 = no epoch, valid only when the
#                             stored d matches the current layer)
# consensus reuse: cpred lives in the bandq half (biased +1), order
# in the glast half, and the 32-bit score array aliases the path
# tape (dead until the consensus backtrack).
_SCRATCH_PER_WIN = ("preds", "succs", "ring", "accs",
                    "arga", "aligsm", "predwv", "bnsq", "anms",
                    "nxgl", "pcsc", "gcbq", "predsm", "path", "regs")

_INF16 = np.int32(0xFFFF)     # minsucc "no successor" sentinel


def _kernel(nlay_ref, bblen_ref,
            seqs_ref, wts_ref, meta_ref,
            cons_ref, mout_ref, *scr,
            v: int, lp: int, d1: int, p: int, s_: int, a_: int,
            k: int, wb: int, s_win: int, krank: int,
            match: int, mismatch: int, gap: int,
            wtype: int, trim: int, prof: int = 0):
    S = s_win
    i = pl.program_id(0)
    nlay_u = [nlay_ref[S * i + u] for u in range(S)]
    bbl_u = [bblen_ref[S * i + u] for u in range(S)]
    # every per-window array is its own ref: the S windows' walks
    # interleave in one straight-line region, and DISTINCT refs are
    # what lets the scheduler prove window B's loads cannot alias
    # window A's stores (a shared ref with u*v offsets serializes the
    # group -- measured r5: zero speedup from pairing until the split)
    grp = {}
    for gi, name in enumerate(_SCRATCH_PER_WIN):
        grp[name] = tuple(scr[gi * S + u] for u in range(S))
    chw_v, chars_v, chw_s, cons_sm, sem = \
        scr[len(_SCRATCH_PER_WIN) * S:]
    preds_u = grp["preds"]
    succs_u = grp["succs"]
    ring_u = grp["ring"]
    accs_u = grp["accs"]
    arga_u = grp["arga"]
    aligsm_u = grp["aligsm"]
    predwv_u = grp["predwv"]
    bnsq_u = grp["bnsq"]
    anms_u = grp["anms"]
    nxgl_u = grp["nxgl"]
    pcsc_u = grp["pcsc"]
    gcbq_u = grp["gcbq"]
    predsm_u = grp["predsm"]
    path_u = grp["path"]
    regs_u = grp["regs"]
    # consensus score is the one per-node field needing 32 bits; it
    # aliases the path tape, dead until the consensus backtrack (the
    # backtrack only starts after the forward DP's last score read)
    score_u = path_u

    M16 = jnp.int32(0xFFFF)
    NM16 = jnp.int32(-65536)          # ~M16: keep-hi mask

    def lo16(x):
        """Unsigned lo half of a packed word."""
        return x & M16

    def hi16(x):
        """Unsigned hi half of a packed word (mask because the int32
        arithmetic shift sign-extends when the hi field's top bit is
        set, e.g. the 0xFFFF minsucc sentinel)."""
        return (x >> 16) & M16

    def stage_chw():
        """Copy the staged packed char*256+weight rows into SMEM: the
        merge/seed phases read row u per position, and a scalar SMEM
        read is ~20 ns where each vector->scalar lane extraction costs
        a VPU sync -- the round-3 merge bottleneck.  The copy moves
        the whole (8, lp+256) staging block because DMA slices must be
        8-sublane aligned; rows S..7 are ballast."""
        cp = pltpu.make_async_copy(chw_v, chw_s, sem)
        cp.start()
        cp.wait()
    q = 128               # band-start quantum: 128-aligned lane slices
                          # are free; 64-offset slices cost a rotation
    tape = v + lp
    negf = jnp.float32(-float(_BIG))
    matchf = jnp.float32(match)
    mismatchf = jnp.float32(mismatch)
    gapf = jnp.float32(gap)
    cols_i = lax.broadcasted_iota(jnp.int32, (1, wb), 1)
    colsf = cols_i.astype(jnp.float32)
    colsg = colsf * jnp.float32(gap)
    iota_p = lax.broadcasted_iota(jnp.int32, (1, p), 1)
    iota_s = lax.broadcasted_iota(jnp.int32, (1, s_), 1)
    iota_a = lax.broadcasted_iota(jnp.int32, (1, a_), 1)
    # path pack radix: entry = (node+2)*pkr + (spos+2); spos < lp and
    # node < v, so pkr must clear lp (the wrapper asserts the product
    # fits int32)
    pkr = 1
    while pkr < lp + 8:
        pkr <<= 1

    def e11(val2d):
        """(1,1) value -> scalar."""
        return val2d[0, 0]

    def vload(ref, row):
        return ref[pl.ds(row, 1), :]

    def min_idx(mask, width, iota_row):
        """First lane index where mask is true, else width."""
        return e11(jnp.min(jnp.where(mask, iota_row, width),
                           axis=1, keepdims=True))

    # ---- scratch bulk init (scratch persists across grid programs) --
    iota_v0 = lax.broadcasted_iota(jnp.int32, (v, 1), 0)
    bblm_u = [jnp.minimum(bbl_u[u], v) for u in range(S)]
    for u in range(S):
        # backbone chain adjacency, vectorized (one column store each)
        preds_u[u][:, :] = jnp.full((v, p), -1, jnp.int32)
        preds_u[u][:, 0:1] = jnp.where(
            (iota_v0 > 0) & (iota_v0 < bblm_u[u]), iota_v0 - 1, -1)
        succs_u[u][:, :] = jnp.full((v, s_), -1, jnp.int32)
        succs_u[u][:, 0:1] = jnp.where(
            iota_v0 < bblm_u[u] - 1, iota_v0 + 1, -1)
    chw_v[:, :] = jnp.zeros((8, lp + 256), jnp.int32)
    chars_v[:, :] = jnp.zeros((8, lp + 256), jnp.int32)

    def init_nodes(j, _):
        for u in range(S):
            # gcnt 0, bandq no-epoch -- one packed store per node
            gcbq_u[u][j] = jnp.int32(0)
        return 0

    lax.fori_loop(0, v, init_nodes, 0)

    # regs: 0 fail, 1 head, 2 nodes_len, 3 n_seqs_incl, 4 rank_steps,
    # 6 best sink node, 7 best sink score, 8 nreal, 9 nbad, 10 target
    for u in range(S):
        regs_u[u][0] = jnp.int32(0)
        regs_u[u][1] = jnp.int32(0)
        regs_u[u][2] = bblm_u[u]
        regs_u[u][3] = jnp.int32(1)
        regs_u[u][4] = jnp.int32(0)

        @pl.when(bbl_u[u] > v)
        def _(u=u):
            regs_u[u][0] = jnp.int32(FAIL_VCAP)

    # ---- seed the backbone chains (add_alignment with an empty path:
    # racon_tpu/native/poa_graph.hpp add_alignment initial branch) ----
    # stage char*256+weight in VMEM (the DP band load windows into it)
    # and mirror it into SMEM (seed/merge read per position)
    for u in range(S):
        chw_v[u:u + 1, 0:lp] = seqs_ref[u, 0:1, :] * 256 \
            + wts_ref[u, 0:1, :]
    stage_chw()

    def chw_at(u, j):
        """(char, weight) at dynamic position j: scalar SMEM reads of
        the mirrored row, no VPU involvement."""
        x = chw_s[u, j]
        return x // 256, x % 256

    def seed_one(u, j, prev_w, act):
        c, w = chw_at(u, j)

        @pl.when(act)
        def _():
            has_nxt = j + 1 < bbl_u[u]
            bnsq_u[u][j] = c | (1 << 16)              # base, nseq=1
            anms_u[u][j] = j | (jnp.where(has_nxt, j + 1,
                                          _INF16) << 16)
            nxgl_u[u][j] = jnp.where(has_nxt, j + 2, 0) \
                | (j << 16)                           # nxt+1, glast=j
            pcsc_u[u][j] = jnp.where(j > 0, 1, 0) \
                | (jnp.where(has_nxt, 1, 0) << 16)
            predsm_u[u][(j) * 8 + 0] = j - 1

            @pl.when(j > 0)
            def _():
                # chain ids/anchors were written vectorized above;
                # only the data-dependent weight is per-node
                # (pred-side only: consensus scores in-edges, so succ
                # weights would be dead state)
                wrow = vload(predwv_u[u], j)
                predwv_u[u][pl.ds(j, 1), :] = jnp.where(
                    iota_p == 0, prev_w + w, wrow)
        return jnp.where(act, w, prev_w)

    def seed(j, carry):
        ws = list(carry)
        for u in range(S):
            ws[u] = seed_one(u, j, ws[u], j < bblm_u[u])
        return tuple(ws)

    bblm_max = bblm_u[0]
    for u in range(1, S):
        bblm_max = jnp.maximum(bblm_max, bblm_u[u])
    lax.fori_loop(0, bblm_max, seed, (jnp.int32(0),) * S)

    # ---- helpers shared by the merge step (u is a python int) -------

    def insert_after(u, pos, node):
        """Linked-list insert; pos == -1 -> new head.  nxt lives in
        the lo half of nxgl (biased +1, 0 = end of list)."""
        @pl.when(pos >= 0)
        def _():
            w_pos = nxgl_u[u][pos]
            nxgl_u[u][node] = (nxgl_u[u][node] & NM16) | (w_pos & M16)
            nxgl_u[u][pos] = (w_pos & NM16) | (node + 1)

        @pl.when(pos < 0)
        def _():
            nxgl_u[u][node] = (nxgl_u[u][node] & NM16) \
                | (regs_u[u][1] + 1)
            regs_u[u][1] = node

    def new_node(u, c, anchor, pos):
        """Allocate a node and insert it after list position pos."""
        nid = regs_u[u][2]
        ok = nid < v

        @pl.when(ok)
        def _():
            bnsq_u[u][nid] = c                   # base; nseq = 0
            anms_u[u][nid] = anchor | NM16       # minsucc = 0xFFFF
            nxgl_u[u][nid] = nid << 16           # no nxt; glast = nid
            gcbq_u[u][nid] = jnp.int32(0)        # gcnt 0, no epoch
            pcsc_u[u][nid] = jnp.int32(0)
            # slot 0 must be initialized: a zero-pred node's traceback
            # diag code still reads mirror slot 0 (cnt-bounded readers
            # cover slots >= 1 only)
            predsm_u[u][(nid) * 8 + 0] = jnp.int32(-1)
            regs_u[u][2] = nid + 1
            insert_after(u, pos, nid)

        @pl.when(jnp.logical_not(ok) & (regs_u[u][0] == 0))
        def _():
            regs_u[u][0] = jnp.int32(FAIL_VCAP)
        return jnp.where(ok, nid, 0)

    def add_edge(u, nu, t, w):
        """poa_graph.hpp add_edge: accumulate weight on an existing
        nu->t edge else append.  The hit search walks t's <=8-slot
        PRED id mirror in SMEM (scalar reads, no vector->scalar sync;
        in-degree is 1 for most nodes so the first probe usually
        decides); the weight accumulate is a masked vector add on the
        node's VMEM weight row -- no scalar extraction either way.
        Only the pred-side weight exists: consensus scores in-edges
        only."""
        pc_ = lo16(pcsc_u[u][t])
        found = jnp.int32(-1)
        for pp in range(7, -1, -1):     # descending: first hit wins
            found = jnp.where((pp < pc_) &
                              (predsm_u[u][(t) * 8 + pp] == nu),
                              pp, found)

        def deep_search(_):
            # rare: in-degree > 8, search the full VMEM id row
            prow = vload(preds_u[u], t)
            return min_idx(prow == nu, p, iota_p)

        def mirror_hit(_):
            return jnp.where(found >= 0, found, p)

        hit = lax.cond((found < 0) & (pc_ > 8), deep_search,
                       mirror_hit, 0)

        @pl.when(hit < p)
        def _():
            wrow = vload(predwv_u[u], t)
            predwv_u[u][pl.ds(t, 1), :] = jnp.where(
                iota_p == hit, wrow + w, wrow)

        @pl.when(hit >= p)
        def _():
            free = hi16(pcsc_u[u][nu])
            prow = vload(preds_u[u], t)
            pfree = lo16(pcsc_u[u][t])
            okk = (free < s_) & (pfree < p)

            @pl.when(okk)
            def _():
                srow = vload(succs_u[u], nu)
                succs_u[u][pl.ds(nu, 1), :] = jnp.where(
                    iota_s == free, t, srow)
                wam = anms_u[u][nu]
                ms = jnp.minimum(hi16(wam), lo16(anms_u[u][t]))
                anms_u[u][nu] = (wam & M16) | (ms << 16)
                preds_u[u][pl.ds(t, 1), :] = jnp.where(
                    iota_p == pfree, nu, prow)
                pcsc_u[u][nu] = (pcsc_u[u][nu] & M16) \
                    | ((free + 1) << 16)
                pcsc_u[u][t] = (pcsc_u[u][t] & NM16) | (pfree + 1)
                wrow = vload(predwv_u[u], t)
                predwv_u[u][pl.ds(t, 1), :] = jnp.where(
                    iota_p == pfree, w, wrow)

                @pl.when(pfree < 8)
                def _():
                    predsm_u[u][(t) * 8 + 0 + pfree] = nu

            @pl.when(jnp.logical_not(okk) & (regs_u[u][0] == 0))
            def _():
                # don't overwrite an earlier fail (a vcap overflow
                # returns node 0 as the merge target, whose slots then
                # overflow too -- without the guard every vcap reject
                # gets misreported as a pcap reject)
                regs_u[u][0] = jnp.int32(FAIL_EDGE)

    # ---- per-layer loop (joint over the pair) -----------------------

    def layer(d, _):
        act_u = [(regs_u[u][0] == 0) & (d <= nlay_u[u])
                 for u in range(S)]

        act_any = act_u[0]
        for u in range(1, S):
            act_any = act_any | act_u[u]

        @pl.when(act_any)
        def _do_layer():
            # per-window layer metadata (meta rows exist for every
            # d < d1, so reads past a window's own nlay are safe and
            # their uses are act-gated)
            begin_u = [meta_ref[u, d, 0] for u in range(S)]
            end_u = [meta_ref[u, d, 1] for u in range(S)]
            fsp_u = [meta_ref[u, d, 2] for u in range(S)]
            m_u = [meta_ref[u, d, 3] for u in range(S)]
            for u in range(S):
                regs_u[u][3] = regs_u[u][3] + jnp.where(
                    act_u[u] & (m_u[u] > 0), 1, 0)
                # stage chars (DP band loads) and char*256+weight
                # (SMEM mirror for the merge) once per layer
                chars_v[u:u + 1, 0:lp] = seqs_ref[u, pl.ds(d, 1), :]
                chw_v[u:u + 1, 0:lp] = chars_v[u:u + 1, 0:lp] * 256 \
                    + wts_ref[u, pl.ds(d, 1), :]
            stage_chw()

            # 1+2) fused walk + banded DP: ONE joint pass over both
            # windows' topo lists; each joint iteration runs one rank
            # of each window so the two serial score chains interleave
            # in a single straight-line region (the whole point of
            # pairing, see module docstring).  Band placement is
            # rank-based from the carried in-subset counter: sq is
            # monotone along the topo list, so a successor's band
            # never lags any predecessor's (the dq >= 0 invariant).
            # full-span sentinel is 0xFFFE: minsucc is a 16-bit field
            # now, real anchors are <= lp << 0xFFFE, and only the
            # 0xFFFF no-successor sentinel exceeds it
            end_eff_u = [jnp.where(fsp_u[u] > 0,
                                   jnp.int32(0xFFFE), end_u[u])
                         for u in range(S)]
            smax_u = [(jnp.maximum(m_u[u] + 1 - wb, 0) + q - 1) // q
                      for u in range(S)]
            # q8 fixed-point band slope per subset rank: nr is the
            # list length for full-span layers (their subset is the
            # whole graph) and a backbone-density estimate for partial
            # layers; one multiply+shift per rank replaces a dynamic
            # divide (nvis <= v, slope < 2^18 only when nr_est is 1
            # and m is at cap -- products stay inside int32)
            slope_u = []
            for u in range(S):
                span = jnp.maximum(end_u[u] - begin_u[u], 1)
                nr_est = jnp.where(
                    fsp_u[u] > 0, regs_u[u][2],
                    jnp.maximum(1, (span * regs_u[u][2])
                                // bblm_u[u]))
                slope_u.append((m_u[u] * 256)
                               // jnp.maximum(nr_est, 1))
                regs_u[u][6] = jnp.int32(-1)    # best sink node
                # sink-score floor: unreachable rows hold clipped
                # -inf (-2^24 after the pack clip), so the init must
                # sit ABOVE that (else a sink whose end column only
                # ever received propagated -inf would win the fold
                # and the no-reachable-sink reject below could never
                # fire) yet below any real score
                # (|score| <= max|param| * (v + lp) << 2^22)
                regs_u[u][7] = jnp.int32(-(1 << 22))

            def slot_meta(u, pid, cnt, t):
                """(epoch-valid, band-start) for one pred slot."""
                be = hi16(gcbq_u[u][jnp.clip(pid, 0, v - 1)])
                valid = (t < cnt) & (pid >= 0) & ((be >> 8) == d)
                return valid, jnp.where(valid, be & 255, 0)

            def pred_fold(u, pid, valid, sqp, sq_r):
                """One predecessor's H row realigned to this rank's
                band, in vert space (u[c] = H_pred[s_r + c]); the diag
                view is u shifted by one, applied once per rank after
                the fold since the shift commutes with the max.

                dq (the band lag) is < _N_SHIFT quanta, so the
                realignment is a SELECT over the 4 static left-shifted
                views of the row -- pure register ops.  (The r4 design
                staged the row into a scratch ref and re-read it at a
                dynamic lane offset; that VMEM write->dynamic-read
                round trip stalled the pipeline once per slot per
                rank and dominated the kernel wall.)"""
                dq = sq_r - sqp
                ok = valid & (dq >= 0) & (dq < _N_SHIFT)
                hvp = ring_u[u][pl.ds(jnp.clip(pid, 0, v - 1), 1), :]
                # unpack the score (arithmetic >> 6 floors negatives
                # correctly since the packed code is non-negative)
                h0 = (hvp >> 6).astype(jnp.float32)
                hv = h0
                for kq in range(1, _N_SHIFT):
                    shk = jnp.pad(h0, ((0, 0), (0, kq * q)),
                                  constant_values=negf)[:, kq * q:
                                                        kq * q + wb]
                    hv = jnp.where(dq == kq, shk, hv)
                hv = jnp.where(ok, hv, negf)
                # a predecessor whose band lags out of shift range
                # cannot contribute; silently degrading would corrupt
                # the consensus, so the window must fail to the CPU
                # engine (the lockstep path's kcap reject analog)
                bad = valid & jnp.logical_not(ok)
                return hv, jnp.where(valid, 1, 0), bad

            def acc_update(u, hv, t):
                a0 = accs_u[u][0:1, :]
                up = hv > a0
                accs_u[u][0:1, :] = jnp.where(up, hv, a0)
                arga_u[u][0:1, :] = jnp.where(up, t, arga_u[u][0:1, :])

            def dp_pre(u, node, nvis):
                """Scalar prolog + first-slot fold for one rank of
                window u; node -1 = walk done (inert).  Pure compute
                with clamped indices (garbage-safe): the two windows'
                prologs run back to back in one basic block."""
                live = node >= 0
                nodec = jnp.maximum(node, 0)
                wam = anms_u[u][nodec]
                anc = lo16(wam)
                in_sub = live & act_u[u] & (
                    (fsp_u[u] > 0) |
                    ((anc >= begin_u[u]) & (anc <= end_u[u])))
                cnt = lo16(pcsc_u[u][nodec])
                # subset SINKS snap to the last quantum: their row is
                # only ever read at column m - s_r (the inline sink
                # fold below), and the floor-quantized interpolation
                # can misplace by up to q-1 columns, which at narrow
                # bands would push the end column out of reach
                is_sink_n = hi16(wam) > end_eff_u[u]
                sq_r = jnp.where(
                    is_sink_n, smax_u[u],
                    jnp.clip(
                        (((nvis * slope_u[u]) >> 8) - (q // 2)) >> 7,
                        0, smax_u[u]))
                s_r = sq_r * q
                pid0 = jnp.where(cnt > 0, predsm_u[u][(nodec) * 8 + 0],
                                 -1)
                val0, sqp0 = slot_meta(u, pid0, cnt, 0)
                pid1 = predsm_u[u][(nodec) * 8 + 1]
                val1, sqp1 = slot_meta(u, pid1, cnt, 1)
                pid2 = predsm_u[u][(nodec) * 8 + 2]
                val2, sqp2 = slot_meta(u, pid2, cnt, 2)
                pid3 = predsm_u[u][(nodec) * 8 + 3]
                val3, sqp3 = slot_meta(u, pid3, cnt, 3)
                vvb = s_r.astype(jnp.float32) * gapf

                hv0, nv0, bad0 = pred_fold(u, pid0, val0, sqp0,
                                           sq_r)
                hv1, nv1, bad1 = pred_fold(u, pid1, val1, sqp1,
                                           sq_r)
                hv2, nv2, bad2 = pred_fold(u, pid2, val2, sqp2,
                                           sq_r)
                hv3, nv3, bad3 = pred_fold(u, pid3, val3, sqp3,
                                           sq_r)
                # first-slot-wins argmax tree (matches the former
                # sequential strict-> update order exactly)
                a01 = jnp.maximum(hv0, hv1)
                g01 = jnp.where(hv1 > hv0, 1, 0)
                a23 = jnp.maximum(hv2, hv3)
                g23 = jnp.where(hv3 > hv2, 3, 2)
                accf = jnp.maximum(a01, a23)
                argf = jnp.where(a23 > a01, g23, g01)
                return dict(node=node, nvis=nvis, live=live,
                            nodec=nodec, in_sub=in_sub, cnt=cnt,
                            is_sink_n=is_sink_n, sq_r=sq_r, s_r=s_r,
                            vvb=vvb, accf=accf, argf=argf,
                            nv03=nv0 + nv1 + nv2 + nv3,
                            nbad03=(jnp.where(bad0, 1, 0)
                                    + jnp.where(bad1, 1, 0)
                                    + jnp.where(bad2, 1, 0)
                                    + jnp.where(bad3, 1, 0)),
                            deep=cnt > 4,
                            nxt=jnp.where(live & act_u[u],
                                          lo16(nxgl_u[u][nodec]) - 1,
                                          -1),
                            nvis2=nvis + jnp.where(in_sub, 1, 0))

            def dp_deep(u, st):
                """Slots 4+ fold (rare: in-degree > 4), in its own
                act-gated region; folds on top of the slot 0-3 tree
                into accs/arga + regs 8."""
                in_sub, deep_c = st["in_sub"], st["deep"]
                nodec, cnt = st["nodec"], st["cnt"]
                sq_r = st["sq_r"]

                @pl.when(in_sub & deep_c)
                def _():
                    regs_u[u][8] = jnp.int32(0)   # nreal slots 4+
                    accs_u[u][0:1, :] = st["accf"]
                    arga_u[u][0:1, :] = st["argf"]
                    prow = vload(preds_u[u], nodec)

                    def deep_step(t, nr2):
                        pid = e11(jnp.sum(
                            jnp.where(iota_p == t, prow, 0),
                            axis=1, keepdims=True))
                        val, sqp = slot_meta(u, pid, cnt, t)
                        hv, nv, bad = pred_fold(u, pid, val, sqp,
                                                sq_r)
                        acc_update(u, hv, t)

                        @pl.when(bad)
                        def _():
                            regs_u[u][0] = jnp.int32(FAIL_KCAP)
                        return nr2 + nv

                    regs_u[u][8] = lax.fori_loop(4, cnt, deep_step,
                                                 jnp.int32(0))

            def dp_epi(u, st):
                """Pure epilogue: the serial gap-chain.  Both windows'
                epilogues are emitted back to back with no region
                boundary between them, so the VLIW scheduler can fill
                one chain's latency stalls with the other's ops."""
                nodec, deep_c, vvb = st["nodec"], st["deep"], st["vvb"]
                s_r = st["s_r"]
                nreal = st["nv03"] + jnp.where(deep_c, regs_u[u][8], 0)
                nbad = st["nbad03"]
                novel = nreal == 0
                accu = jnp.where(novel, colsg + vvb,
                                 jnp.where(deep_c, accs_u[u][0:1, :],
                                           st["accf"]))
                argu = jnp.where(novel, 0,
                                 jnp.where(deep_c, arga_u[u][0:1, :],
                                           st["argf"]))
                sb = chars_v[u:u + 1, pl.ds(pl.multiple_of(s_r, q),
                                            wb)]
                sub_u = jnp.where(sb == lo16(bnsq_u[u][nodec]),
                                  matchf, mismatchf)
                dmax_u = accu + sub_u
                vmax = accu + gapf
                dmax = jnp.pad(dmax_u, ((0, 0), (1, 0)),
                               constant_values=negf)[:, :wb]
                t_best = jnp.maximum(dmax, vmax)
                x = t_best - colsg
                if not (prof & 2):   # profiling: skip the gap chain
                    sh = 1
                    while sh < wb:
                        x = jnp.maximum(
                            x, jnp.pad(x, ((0, 0), (sh, 0)),
                                       constant_values=negf)[:, :wb])
                        sh <<= 1
                hr = x + colsg
                argd = jnp.pad(argu, ((0, 0), (1, 0)),
                               constant_values=0)[:, :wb]
                code = jnp.where(
                    dmax == hr, argd,
                    jnp.where(vmax == hr, argu + p,
                              2 * p)).astype(jnp.int32)
                # pack score and direction code into ONE row (halves
                # the dominant VMEM scratch and saves a store): codes
                # are < 2p+1 <= 33 < 64, scores are exact ints well
                # under 2^24 (|score| <= |gap|*(v+lp)); -inf clamps to
                # -2^24, still far below any reachable score
                hpk = (jnp.clip(hr, -float(1 << 24),
                                float(1 << 24)).astype(jnp.int32)
                       * 64 + code)
                return hr, hpk, nbad

            def dp_store(u, st, hr, hpk, nbad):
                """Gated stores + sink fold for one rank."""
                in_sub, nodec = st["in_sub"], st["nodec"]
                sq_r, s_r = st["sq_r"], st["s_r"]

                @pl.when(in_sub)
                def _():
                    ring_u[u][pl.ds(nodec, 1), :] = hpk
                    gcbq_u[u][nodec] = (gcbq_u[u][nodec] & M16) \
                        | (((d << 8) | sq_r) << 16)

                    @pl.when(nbad > 0)
                    def _():
                        regs_u[u][0] = jnp.int32(FAIL_KCAP)

                    # inline sink fold: only true subset sinks pay the
                    # vector->scalar score extraction
                    @pl.when(st["is_sink_n"])
                    def _sink():
                        c_end = m_u[u] - s_r

                        @pl.when(c_end < wb)
                        def _():
                            ccl = jnp.clip(c_end, 0, wb - 1)
                            s_end = jnp.sum(jnp.where(
                                cols_i == ccl, hr,
                                jnp.float32(0))).astype(jnp.int32)

                            @pl.when(s_end > regs_u[u][7])
                            def _():
                                regs_u[u][7] = s_end
                                regs_u[u][6] = st["node"]

            def dp_cond(c):
                alive = c[0] >= 0
                for u in range(1, S):
                    alive = alive | (c[2 * u] >= 0)
                return alive

            def dp_body(c):
                # phase-by-phase across ALL windows: each phase's S
                # bodies are emitted back to back in one straight-line
                # region so the VLIW scheduler can interleave the
                # independent chains (the whole point of grouping).
                # Multi-rank stepping: krank ranks of every window per
                # iteration -- backbone runs of single-pred nodes (the
                # common case) keep every unrolled step productive,
                # and inert tail steps (node -1) are fully gated
                c = list(c)
                for _kr in range(krank):
                    sts = [dp_pre(u, c[2 * u], c[2 * u + 1])
                           for u in range(S)]
                    for u in range(S):
                        dp_deep(u, sts[u])
                    es = [dp_epi(u, sts[u]) for u in range(S)]
                    for u in range(S):
                        dp_store(u, sts[u], *es[u])
                    for u in range(S):
                        c[2 * u] = sts[u]["nxt"]
                        c[2 * u + 1] = sts[u]["nvis2"]
                return tuple(c)

            head_u = [jnp.where(act_u[u], regs_u[u][1], -1)
                      for u in range(S)]
            init = []
            for u in range(S):
                init.extend((head_u[u], jnp.int32(0)))
            fin = lax.while_loop(dp_cond, dp_body, tuple(init))
            nvis_u = [fin[2 * u + 1] for u in range(S)]
            for u in range(S):
                regs_u[u][4] = regs_u[u][4] + nvis_u[u]

                # no subset sink landed within band reach of the
                # layer end: tracing back from node -1 would fabricate
                # an all-new path, so the window must fail to the CPU
                # engine instead
                @pl.when(act_u[u] & (regs_u[u][6] < 0) &
                         (nvis_u[u] > 0))
                def _(u=u):
                    regs_u[u][0] = jnp.int32(FAIL_KCAP)

            # 3) traceback -> reversed path in path_s, packed as
            # (node+2)*pkr + (spos+2); node -1 = no node (horiz),
            # carried node -1 = virtual start row.  Joint loop: both
            # windows' steps interleave so the per-step extract
            # latencies overlap.
            tact_u = [act_u[u] & (regs_u[u][0] == 0)
                      for u in range(S)]
            if prof & 1:   # profiling: skip traceback+merge
                tact_u = [jnp.bool_(False) for _ in range(S)]

            def tb_pre(u, node, jj, step, live):
                """Pure step compute (incl. the per-step direction
                extract, the latency to hide); both windows' pres run
                in one block."""
                nodec = jnp.maximum(node, 0)
                be = hi16(gcbq_u[u][nodec])
                s0 = jnp.where(node >= 0, be & 255, 0) * q
                cc = jnp.clip(jj - s0, 0, wb - 1)
                drow = ring_u[u][pl.ds(nodec, 1), :]
                code = jnp.sum(jnp.where(cols_i == cc, drow, 0)) % 64
                is_diag = (code < p) & (node >= 0)
                is_vert = (code >= p) & (code < 2 * p) & (node >= 0)
                take = is_diag | is_vert
                slot = jnp.clip(jnp.where(is_diag, code, code - p),
                                0, p - 1)
                pidm = predsm_u[u][(nodec) * 8
                                   + jnp.clip(slot, 0, 7)]
                return dict(node=node, jj=jj, step=step, live=live,
                            nodec=nodec, take=take, is_vert=is_vert,
                            slot=slot, pidm=pidm)

            def tb_fin(u, st):
                node, jj, step = st["node"], st["jj"], st["step"]
                live, nodec = st["live"], st["nodec"]
                take, is_vert = st["take"], st["is_vert"]
                slot = st["slot"]

                def deep(_):
                    prow = vload(preds_u[u], nodec)
                    return jnp.sum(jnp.where(iota_p == slot, prow, 0))

                def keep(_):
                    return st["pidm"]

                pid = lax.cond(slot >= 8, deep, keep, 0)
                pvalid = (pid >= 0) & \
                    ((hi16(gcbq_u[u][jnp.clip(pid, 0, v - 1)]) >> 8)
                     == d)
                pnode = jnp.where(pvalid, pid, -1)
                en = jnp.where(take, node, -1)
                es = jnp.where(is_vert, -1, jj - 1)

                @pl.when(live)
                def _():
                    path_u[u][jnp.clip(step, 0, tape - 1)] = \
                        (en + 2) * pkr + (es + 2)
                nn2 = jnp.where(take, pnode, node)
                nj = jnp.where(is_vert, jj, jnp.maximum(jj - 1, 0))
                return (jnp.where(live, nn2, node),
                        jnp.where(live, nj, jj),
                        step + jnp.where(live, 1, 0))

            def tb_live(c, u):
                n, j, sc = c[3 * u], c[3 * u + 1], c[3 * u + 2]
                return ((n >= 0) | (j > 0)) & (sc < tape)

            def tb_cond(c):
                alive = tb_live(c, 0)
                for u in range(1, S):
                    alive = alive | tb_live(c, u)
                return alive

            def tb_body(c):
                sts = [tb_pre(u, c[3 * u], c[3 * u + 1], c[3 * u + 2],
                              tb_live(c, u))
                       for u in range(S)]
                out = []
                for u in range(S):
                    out.extend(tb_fin(u, sts[u]))
                return tuple(out)

            tb0 = [jnp.where(tact_u[u], regs_u[u][6], -1)
                   for u in range(S)]
            tbm = [jnp.where(tact_u[u], m_u[u], 0) for u in range(S)]
            init_tb = []
            for u in range(S):
                init_tb.extend((tb0[u], tbm[u], jnp.int32(0)))
            fin_tb = lax.while_loop(tb_cond, tb_body, tuple(init_tb))
            plen_u = [fin_tb[3 * u + 2] for u in range(S)]
            for u in range(S):
                @pl.when(tact_u[u] & (plen_u[u] >= tape))
                def _(u=u):
                    regs_u[u][0] = jnp.int32(FAIL_PATH)

            # 4) merge (poa_graph.hpp add_alignment), walking the
            # reversed path backward = forward order; chars/weights
            # come from the rows staged at layer start.  Joint loop:
            # the two windows' scalar chase chains interleave.
            mact_u = [act_u[u] & (regs_u[u][0] == 0)
                      for u in range(S)]
            mlen_u = [jnp.where(mact_u[u], plen_u[u], 0)
                      for u in range(S)]

            def m_pre(u, t, prev, prev_w):
                """Pure step decode (the scalar chase chain); both
                windows' pres run in one block."""
                act = t < mlen_u[u]
                packed = path_u[u][jnp.clip(mlen_u[u] - 1 - t, 0,
                                            tape - 1)]
                nid = packed // pkr - 2
                jj = packed % pkr - 2
                has = act & (jj >= 0)
                # clamp to the staged row: an inactive lane decodes a
                # garbage path slot, and OOB SMEM reads are UB even
                # when the result is masked out
                c, w = chw_at(u, jnp.clip(jj, 0, lp - 1))
                fast = has & (nid >= 0) & \
                    (lo16(bnsq_u[u][jnp.clip(nid, 0, v - 1)]) == c)
                return dict(prev=prev, prev_w=prev_w, nid=nid,
                            has=has, c=c, w=w, fast=fast)

            def m_apply(u, st):
                # flattened per-step control flow: the dominant case
                # (match into an existing same-base node) runs with no
                # lax.cond; rare cases (insertion, mismatch into an
                # aligned group) sit behind one pl.when
                prev, prev_w = st["prev"], st["prev_w"]
                nid, has = st["nid"], st["has"]
                c, w, fast = st["c"], st["w"], st["fast"]
                regs_u[u][10] = nid  # resolved target (fast case)

                @pl.when(has & jnp.logical_not(fast))
                def _slow():
                    def t_new(_):
                        anchor = jnp.where(
                            prev < 0, begin_u[u],
                            lo16(anms_u[u][jnp.maximum(prev, 0)]))
                        pos = jnp.where(
                            prev < 0, -1,
                            hi16(nxgl_u[u][jnp.maximum(prev, 0)]))
                        return new_node(u, c, anchor, pos)

                    def t_aligned(_):
                        # mismatch: reuse an aligned sibling with the
                        # same base else create one (poa_graph.hpp
                        # aligned-group branch).  Group lists live in
                        # VMEM as (sib * 256 + sib_base) entries: the
                        # base tag makes the same-base search one
                        # vector compare + extract, and group members
                        # have distinct bases by construction so at
                        # most one entry matches
                        gc = lo16(gcbq_u[u][nid])
                        arow = vload(aligsm_u[u], nid)
                        h = e11(jnp.min(jnp.where(
                            (arow % 256 == c) & (iota_a < gc),
                            arow // 256, v), axis=1, keepdims=True))
                        found = jnp.where(h < v, h, -1)

                        def mk_new(_):
                            tgt = new_node(
                                u, c, lo16(anms_u[u][nid]),
                                hi16(nxgl_u[u][nid]))

                            @pl.when(gc >= a_)
                            def _():
                                regs_u[u][0] = \
                                    jnp.int32(FAIL_ALIGNED)

                            @pl.when(gc < a_)
                            def _():
                                # tgt's group = nid's members + nid
                                nb = lo16(bnsq_u[u][nid])
                                aligsm_u[u][pl.ds(tgt, 1), :] = \
                                    jnp.where(iota_a == gc,
                                              nid * 256 + nb, arow)
                                gcbq_u[u][tgt] = \
                                    (gcbq_u[u][tgt] & NM16) | (gc + 1)

                                # append tgt to each member (groups
                                # already full skip the append)
                                def ap(aa, _):
                                    sib = e11(jnp.sum(jnp.where(
                                        iota_a == aa, arow, 0),
                                        axis=1, keepdims=True)) // 256
                                    gs = lo16(gcbq_u[u][sib])

                                    @pl.when(gs < a_)
                                    def _():
                                        srw = vload(aligsm_u[u], sib)
                                        aligsm_u[u][
                                            pl.ds(sib, 1),
                                            :] = jnp.where(
                                                iota_a == gs,
                                                tgt * 256 + c, srw)
                                        gcbq_u[u][sib] = \
                                            (gcbq_u[u][sib] & NM16) \
                                            | (gs + 1)
                                    nxgl_u[u][sib] = \
                                        (nxgl_u[u][sib] & M16) \
                                        | (tgt << 16)
                                    return 0

                                lax.fori_loop(0, gc, ap, 0)
                                aligsm_u[u][pl.ds(nid, 1), :] = \
                                    jnp.where(iota_a == gc,
                                              tgt * 256 + c, arow)
                                gcbq_u[u][nid] = \
                                    (gcbq_u[u][nid] & NM16) | (gc + 1)
                                nxgl_u[u][nid] = \
                                    (nxgl_u[u][nid] & M16) \
                                    | (tgt << 16)
                            return tgt

                        return lax.cond(found >= 0, lambda _: found,
                                        mk_new, 0)

                    regs_u[u][10] = lax.cond(nid < 0, t_new,
                                                t_aligned, 0)

                target = regs_u[u][10]

                @pl.when(has)
                def _():
                    # nseq is the hi half of bnsq: +1<<16 bumps it
                    # without touching the base half
                    bnsq_u[u][target] = bnsq_u[u][target] + (1 << 16)

                    @pl.when(prev >= 0)
                    def _():
                        add_edge(u, prev, target, prev_w + w)

                return (jnp.where(has, target, prev),
                        jnp.where(has, w, prev_w))

            def mbody(t, carry):
                sts = [m_pre(u, t, carry[2 * u], carry[2 * u + 1])
                       for u in range(S)]
                out = []
                for u in range(S):
                    out.extend(m_apply(u, sts[u]))
                return tuple(out)

            mlen_max = mlen_u[0]
            for u in range(1, S):
                mlen_max = jnp.maximum(mlen_max, mlen_u[u])
            lax.fori_loop(0, mlen_max, mbody,
                          (jnp.int32(-1), jnp.int32(0)) * S)
        return 0

    nlay_max = nlay_u[0]
    for u in range(1, S):
        nlay_max = jnp.maximum(nlay_max, nlay_u[u])
    lax.fori_loop(1, nlay_max + 1, layer, 0)

    # ---- consensus: heaviest bundle over each full graph ------------
    for u in range(S):
        fail = regs_u[u][0]
        for r in range(8):
            mout_ref[u, r, 0] = jnp.int32(0)
        mout_ref[u, 0, 0] = jnp.where(fail == 0, 0, -1)
        mout_ref[u, 2, 0] = fail
        mout_ref[u, 3, 0] = regs_u[u][2]
        mout_ref[u, 4, 0] = regs_u[u][4]

        @pl.when(fail == 0)
        def _consensus(u=u):
            # walk the list once for a full topo order; order reuses
            # the glast half of nxgl (group-last is dead by now), so
            # each step is one RMW store next to the lo-half nxt read
            def wcond(c):
                return c[0] >= 0

            def wbody(c):
                node, r = c
                nxgl_u[u][r] = (nxgl_u[u][r] & M16) | (node << 16)
                return lo16(nxgl_u[u][node]) - 1, r + 1

            _, n_all = lax.while_loop(wcond, wbody,
                                      (regs_u[u][1], jnp.int32(0)))

            # forward DP: per node pick the heaviest in-edge (ties ->
            # higher predecessor score; slot order = insertion order,
            # matching poa_graph.hpp consensus_path).  Scores need the
            # full 32 bits, so they alias the path tape (dead until
            # the backtrack below); weights come off the node's VMEM
            # row, loaded once per node
            def cdp(r, best_sink):
                node = hi16(nxgl_u[u][r])
                cnt = lo16(pcsc_u[u][node])
                wrow = vload(predwv_u[u], node)

                def pick(t, carry):
                    bu, bw = carry
                    tc = jnp.clip(t, 0, 7)
                    pidm = predsm_u[u][(node) * 8 + 0 + tc]

                    def deep(_):
                        # spilled slot: id from the VMEM row
                        prow = vload(preds_u[u], node)
                        return e11(jnp.sum(
                            jnp.where(iota_p == t, prow, 0), axis=1,
                            keepdims=True))

                    def keep(_):
                        return pidm

                    pid = lax.cond(t >= 8, deep, keep, 0)
                    w = e11(jnp.sum(
                        jnp.where(iota_p == t, wrow, 0), axis=1,
                        keepdims=True))
                    sc = score_u[u][jnp.maximum(pid, 0)]
                    bsc = score_u[u][jnp.maximum(bu, 0)]
                    tk = (pid >= 0) & ((w > bw) |
                                       ((w == bw) & (bu >= 0) &
                                        (sc > bsc)))
                    return (jnp.where(tk, pid, bu),
                            jnp.where(tk, w, bw))

                best_u, best_w = lax.fori_loop(
                    0, cnt, pick, (jnp.int32(-1), jnp.int32(-1)))
                score_u[u][node] = jnp.where(
                    best_u >= 0,
                    score_u[u][jnp.maximum(best_u, 0)] + best_w, 0)
                # cpred reuses the bandq half of gcbq, biased +1
                # (0 = no predecessor); gcnt is dead, overwrite whole
                gcbq_u[u][node] = (best_u + 1) << 16
                is_sink = hi16(anms_u[u][node]) >= _INF16
                better = is_sink & (
                    (best_sink < 0) |
                    (score_u[u][node] >
                     score_u[u][jnp.maximum(best_sink, 0)]))
                return jnp.where(better, node, best_sink)

            best_sink = lax.fori_loop(0, n_all, cdp, jnp.int32(-1))

            # backtrack (reversed), then emit forward
            def bcond(c):
                return c[0] >= 0

            def bbody(c):
                node, ln = c
                # the path store may clobber score slots, but the
                # forward DP above made its last score read; the
                # chain itself lives in the gcbq cpred half
                path_u[u][ln] = (node + 2) * pkr + 2
                return hi16(gcbq_u[u][node]) - 1, ln + 1

            _, clen = lax.while_loop(bcond, bbody,
                                     (best_sink, jnp.int32(0)))

            # TGS trim (rt_poab_consensus: threshold (n_seqs - 1) / 2)
            avg = (regs_u[u][3] - 1) // 2

            def scan_fwd(t, first):
                node = path_u[u][clen - 1 - t] // pkr - 2
                cov = hi16(bnsq_u[u][node])
                hit = (first < 0) & (cov >= avg)
                return jnp.where(hit, t, first)

            def scan_bwd(t, last):
                node = path_u[u][t] // pkr - 2
                cov = hi16(bnsq_u[u][node])
                hit = (last < 0) & (cov >= avg)
                return jnp.where(hit, clen - 1 - t, last)

            if wtype == 1 and trim:
                cbegin = lax.fori_loop(0, clen, scan_fwd,
                                       jnp.int32(-1))
                cend = lax.fori_loop(0, clen, scan_bwd, jnp.int32(-1))
                chim = (cbegin < 0) | (cend < 0) | (cbegin >= cend)
                cbegin = jnp.where(chim, 0, cbegin)
                cend = jnp.where(chim, clen - 1, cend)
                status = jnp.where(chim, 2, 0).astype(jnp.int32)
            else:
                cbegin = jnp.int32(0)
                cend = clen - 1
                status = jnp.int32(0)

            length = jnp.maximum(cend - cbegin + 1, 0)

            def emit(t, _):
                node = path_u[u][clen - 1 - (cbegin + t)] \
                    // pkr - 2
                cons_sm[u, t // 128, t % 128] = \
                    lo16(bnsq_u[u][node])
                return 0

            lax.fori_loop(0, length, emit, 0)
            mout_ref[u, 0, 0] = length
            mout_ref[u, 1, 0] = status

    # one DMA ships both consensuses to the VMEM output (dynamic-lane
    # scalar stores into VMEM are not lowerable, and an SMEM output
    # window this size gets pathologically padded by the pipeline)
    cpo = pltpu.make_async_copy(cons_sm, cons_ref, sem)
    cpo.start()
    cpo.wait()


@functools.partial(
    jax.jit,
    static_argnums=(5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18,
                    19, 20, 21))
def _poa_full(seqs, wts, meta, nlay, bblen,
              v: int, lp: int, d1: int, p: int, s_: int, a_: int,
              k: int, wb: int, match: int, mismatch: int, gap: int,
              wtype: int, trim: int, s_win: int = 0, krank: int = 0,
              interpret: bool = False, prof: int = 0):
    """seqs/wts: [B, D1, LP] uint8 (d=0 = backbone), meta: [B, D1, 8]
    int32 (begin, end, full_span, slen, ...), nlay/bblen: [B] int32.
    B must be a multiple of the windows-per-program factor ``s_win``
    (0 = pick the largest that fits); ``krank`` is the multi-rank
    stepping factor (0 = policy pick).
    Returns (cons [B, V, 1] int32, mout [B, 8, 1] int32)."""
    b = seqs.shape[0]
    if not s_win:
        s_win = pick_windows_per_program(v, lp, d1, p, s_, a_, wb)
    assert s_win > 0, "shape does not fit the flagship kernel"
    assert b % s_win == 0, \
        f"batch {b} not a multiple of group factor {s_win}"
    if not krank:
        krank = pick_rank_unroll(v, lp, d1, p, s_, a_, wb, s_win)
    pkr = 1
    while pkr < lp + 8:
        pkr <<= 1
    assert (v + 2) * pkr < 2 ** 31, "path packing overflows int32"
    # the packed 16-bit SMEM fields (node ids, anchors, band epochs)
    # must stay in range; every production cap is far inside these
    assert v <= 0x8000 and lp < 0xFFFE and d1 <= 256, \
        "caps overflow the packed 16-bit scalar fields"
    seqs_l = seqs.astype(jnp.int32)
    wts_l = wts.astype(jnp.int32)

    kern = functools.partial(
        _kernel, v=v, lp=lp, d1=d1, p=p, s_=s_, a_=a_, k=k, wb=wb,
        s_win=s_win, krank=krank, match=match, mismatch=mismatch,
        gap=gap, wtype=wtype, trim=trim, prof=prof)
    # one ref PER WINDOW so the scheduler can prove the interleaved
    # walks never alias (see _kernel); order must match
    # _SCRATCH_PER_WIN
    per_win = {
        "preds": pltpu.VMEM((v, p), jnp.int32),
        "succs": pltpu.VMEM((v, s_), jnp.int32),
        "ring": pltpu.VMEM((v, wb), jnp.int32),   # packed score|code
        "accs": pltpu.VMEM((1, wb), jnp.float32),
        "arga": pltpu.VMEM((1, wb), jnp.int32),
        "aligsm": pltpu.VMEM((v, a_), jnp.int32),  # aligned groups
        "predwv": pltpu.VMEM((v, p), jnp.int32),   # pred weights
        "bnsq": pltpu.SMEM((v,), jnp.int32),
        "anms": pltpu.SMEM((v,), jnp.int32),
        "nxgl": pltpu.SMEM((v,), jnp.int32),   # hi half: cons order
        "pcsc": pltpu.SMEM((v,), jnp.int32),
        "gcbq": pltpu.SMEM((v,), jnp.int32),   # hi half: cons cpred
        "predsm": pltpu.SMEM((8 * v,), jnp.int32),  # pred id mirror
        "path": pltpu.SMEM((v + lp,), jnp.int32),   # also cons score
        "regs": pltpu.SMEM((_NREG,), jnp.int32),
    }
    assert set(per_win) == set(_SCRATCH_PER_WIN)
    scratch = []
    for name in _SCRATCH_PER_WIN:
        scratch.extend([per_win[name]] * s_win)
    scratch += [
        pltpu.VMEM((8, lp + 256), jnp.int32),   # staged chr*w
        pltpu.VMEM((8, lp + 256), jnp.int32),   # staged chars
        pltpu.SMEM((8, lp + 256), jnp.int32),   # chw mirror
        pltpu.SMEM((s_win, v // 128, 128), jnp.int32),  # consensus
        pltpu.SemaphoreType.DMA,                # staging sem
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b // s_win,),
        in_specs=[
            pl.BlockSpec((s_win, d1, lp), lambda i, *_: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((s_win, d1, lp), lambda i, *_: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((s_win, d1, 8), lambda i, *_: (i, 0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec((s_win, v // 128, 128),
                         lambda i, *_: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((s_win, 8, 1), lambda i, *_: (i, 0, 0),
                         memory_space=pltpu.SMEM),
        ),
        scratch_shapes=tuple(scratch),
    )
    assert v % 128 == 0, "node cap must be lane-aligned"
    kwargs = {}
    if not interpret:
        # the compiler's stack temporaries for S interleaved
        # straight-line window bodies exceed Mosaic's default 16M
        # scoped-vmem limit from S=3 up; v5e has 128M of VMEM, so
        # grant the kernel a 64M scope (declared scratch + temps)
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=64 << 20)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((b, v // 128, 128), jnp.int32),
                   jax.ShapeDtypeStruct((b, 8, 1), jnp.int32)),
        interpret=interpret,
        **kwargs,
    )(nlay, bblen, seqs_l, wts_l, meta)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "v", "lp", "d1", "p", "s_", "a_", "k",
                     "wb", "match", "mismatch", "gap", "wtype", "trim",
                     "s_win", "krank", "interpret"))
def _poa_full_sharded(seqs, wts, meta, nlay, bblen, *, mesh,
                      v, lp, d1, p, s_, a_, k, wb,
                      match, mismatch, gap, wtype, trim, s_win, krank,
                      interpret):
    """The same kernel sharded over the mesh batch axis with shard_map:
    one compile, XLA places one grid per device, no collectives — the
    TPU-native analog of the reference's fully independent per-device
    batch queues (src/cuda/cudapolisher.cpp:231-243)."""
    from racon_tpu.parallel.mesh_utils import shard_batch_map

    def shard_fn(seqs, wts, meta, nlay, bblen):
        return _poa_full(seqs, wts, meta, nlay, bblen,
                         v, lp, d1, p, s_, a_, k, wb,
                         match, mismatch, gap, wtype, trim, s_win,
                         krank, interpret)

    return shard_batch_map(shard_fn, mesh, 5, 2)(
        seqs, wts, meta, nlay, bblen)


def poa_full_batch(seqs, wts, meta, nlay, bblen, **kw):
    """NumPy-facing wrapper: dispatch + blocking collect.  Returns
    (cons_chars [B, V] int32 np, mout [B, 8] int32 np).  mout rows:
    0 length (-1 = failed -> CPU re-polish), 1 status (2 = chimeric
    warning), 2 fail code, 3 nodes used, 4 total DP rank steps (for
    cells accounting)."""
    return poa_full_dispatch(seqs, wts, meta, nlay, bblen, **kw)()


def _pad_pairs(seqs, wts, meta, nlay, bblen, mult):
    """Pad the batch to a multiple of ``mult`` with inert 1-base
    windows ('A' backbone, no layers)."""
    from racon_tpu.parallel.mesh_utils import pad_to_multiple

    b0 = seqs.shape[0]
    seqs = pad_to_multiple(seqs, mult, 0)
    seqs[b0:, 0, 0] = ord("A")
    wts = pad_to_multiple(wts, mult, 1)
    meta = pad_to_multiple(meta, mult, 0)
    nlay = pad_to_multiple(nlay, mult, 0)
    bblen = pad_to_multiple(bblen, mult, 1)
    return seqs, wts, meta, nlay, bblen


def poa_full_dispatch(seqs, wts, meta, nlay, bblen, *,
                      v, lp, d1, p=16, s=16, a=8, k=128, wb=256,
                      match=5, mismatch=-4, gap=-8, wtype=1, trim=1,
                      mesh=None):
    """Enqueue one megabatch and return a zero-arg ``collect``
    closure.  The upload and kernel run asynchronously after dispatch,
    so a caller can pack (and dispatch) the NEXT megabatch while this
    one computes -- the upload and the host packing then overlap
    device time (the cudapolisher analog runs per-device
    batch queues on threads, src/cuda/cudapolisher.cpp:257-336).

    With a multi-device ``mesh`` the batch axis is sharded across the
    devices (callers pad the batch; this pads further to a mesh-and-
    group multiple with inert 1-base windows)."""
    import threading

    from racon_tpu.parallel.mesh_utils import interpret_mode

    n_dev = len(mesh.devices) if mesh is not None else 1
    interp = interpret_mode()
    b0 = seqs.shape[0]
    s_win = pick_windows_per_program(v, lp, d1, p, s, a, wb)
    assert s_win > 0, "shape does not fit the flagship kernel"
    krank = pick_rank_unroll(v, lp, d1, p, s, a, wb, s_win)
    mult = s_win * n_dev
    if b0 % mult:
        seqs, wts, meta, nlay, bblen = _pad_pairs(
            seqs, wts, meta, nlay, bblen, mult)
    t_disp = _mono()
    if n_dev > 1:
        cons, mout = _poa_full_sharded(
            jnp.asarray(seqs), jnp.asarray(wts), jnp.asarray(meta),
            jnp.asarray(nlay), jnp.asarray(bblen), mesh=mesh,
            v=v, lp=lp, d1=d1, p=p, s_=s, a_=a, k=k, wb=wb,
            match=match, mismatch=mismatch, gap=gap, wtype=wtype,
            trim=trim, s_win=s_win, krank=krank, interpret=interp)
    else:
        from racon_tpu.utils import aot_shelf

        statics = (v, lp, d1, p, s, a, k, wb, match, mismatch, gap,
                   wtype, trim, s_win, krank, interp)

        def build(se, wt, me, nl, bb):
            return _poa_full(se, wt, me, nl, bb, *statics)

        cons, mout = aot_shelf.call(
            ("poa_full", seqs.shape[0]) + statics, __file__, build,
            (jnp.asarray(seqs), jnp.asarray(wts), jnp.asarray(meta),
             jnp.asarray(nlay), jnp.asarray(bblen)))
    # start both device->host copies before blocking on either, so
    # their latencies overlap
    cons.copy_to_host_async()
    mout.copy_to_host_async()

    # host-independent per-dispatch device time: a watcher thread
    # blocks on the outputs the moment the dispatch is enqueued, so
    # the measured span (upload + kernel + download) cannot be
    # inflated by whatever the host does between dispatch and collect
    # (the two-deep pipeline packs the NEXT megabatch there) -- the
    # bench's poa_device_s, distinguishing kernel regressions from
    # host jitter (VERDICT r5 #8)
    span = {}

    def _watch():
        try:
            jax.block_until_ready((cons, mout))
            t_end = _mono()
            span["s"] = t_end - t_disp
            obs_trace.TRACER.add_span(
                "device.poa_megabatch", t_disp, t_end, cat="device",
                lane="device", args={"b": int(b0)})
            obs_devutil.DEVICE_UTIL.record("poa", t_disp, t_end)
        except Exception:
            pass  # dispatch errors surface at collect()

    watcher = threading.Thread(target=_watch, daemon=True,
                               name="racon-poa-devtime")
    watcher.start()

    def collect():
        # slice off pad rows: the contract is [B, ...]
        c = np.asarray(cons)
        watcher.join()
        return (c.reshape(c.shape[0], -1)[:b0, :],
                np.asarray(mout)[:b0, :, 0])

    collect.device_s = lambda: span.get("s", 0.0)
    return collect
