"""Batched POA consensus on TPU (cudapoa-equivalent).

Re-creates, TPU-first, what the reference gets from ClaraGenomicsAnalysis
cudapoa (reference: src/cuda/cudabatch.cpp:52-265): batched partial-order
alignment consensus over windows.  The CUDA design keeps whole POA
graphs resident on the GPU and runs one thread block per window; that
shape does not map to XLA's static-shape compilation model, so the TPU
design splits the work differently:

* **lockstep layers**: all windows of a batch advance one layer per
  round; round ``d`` runs ONE ``jit``-compiled batched DP aligning every
  window's d-th layer against its current graph — the device sees only
  fixed-shape arrays ``[B, V, ...]``;
* **graphs live on the host** in C++ (racon_tpu/native/poa_batch.cpp,
  reusing the CPU engine's PoaGraph): each round exports per-window
  subgraphs (topo-ordered bases, capped predecessor lists, sink flags)
  and applies the device-produced alignment paths (spoa add_alignment
  semantics);
* the DP scan runs over graph ranks; the in-row gap chain is closed
  with an associative max-plus scan, so each row step is pure vector
  work across ``B x (L+1)`` lanes;
* **traceback runs on device** (one gather per step) and only compact
  paths ``[B, V+L, 2]`` travel device->host.

Windows that overflow the caps (graph nodes > vcap, in-degree > pcap)
are failed over to the CPU engine, exactly the reference's rejection
contract (cudabatch.cpp:124-127 -> cudapolisher.cpp:357-386); over-long
layers are skipped and only reduce coverage (cudabatch.cpp:136-155).
"""

from __future__ import annotations

import ctypes
import functools
import threading
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from racon_tpu.obs import devutil as obs_devutil
from racon_tpu.obs import metrics as obs_metrics
from racon_tpu.obs import trace as obs_trace
from racon_tpu.obs import decision as obs_decision
from racon_tpu.ops import cpu as cpu_ops
from racon_tpu.utils.tuning import poa_band_cols, scan_unroll as _unroll

# the sanctioned clock (racon_tpu/obs): phase walls feed only the
# engine's reporting counters, never control flow
_mono = obs_trace.now

_BIG = np.int32(1 << 28)

# traceback tape sentinels (host side)
PATH_NONE = -1      # no node / no seq position in this step
PATH_DONE = -3      # walk finished


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11, 12))
def _poa_kernel(bases, preds, nrows, sinks, seq, slen,
                v: int, l: int, p: int, k: int,
                match: int, mismatch: int, gap: int):
    """Batched global NW of sequences against DAGs in topo-rank order.

    bases: [B, V] uint8 node bases (rank order)
    preds: [B, V, P] int16 predecessor DP-row indices (0 = virtual
           start row, -1 = pad); in-edges reach back at most ``k`` rows
           (enforced by rt_poab_export; violators fall back to CPU)
    nrows: [B] int32 valid rank count
    sinks: [B, V] uint8 sink flags
    seq:   [B, L] uint8 layer bases, slen: [B] int32

    The DP carries only a ring buffer of the last ``k`` score rows (the
    full [B, V, L] matrix never exists), so the per-step state stays
    VMEM-sized; sink scores are folded on the fly.  Returns
    (node_tape, seq_tape): [B, V+L] int32 each, the reversed alignment
    path per lane; node entries are 0-based ranks or PATH_NONE, seq
    entries are positions or PATH_NONE, PATH_DONE after the walk
    reaches the origin.
    """
    b = bases.shape[0]
    cols = jnp.arange(l + 1, dtype=jnp.int32)
    lanes = jnp.arange(b)
    neg = jnp.float32(-_BIG)
    colsf = cols.astype(jnp.float32)

    # virtual start row H[0][j] = j*gap (always addressable as pred 0);
    # scores are exact in f32 (|score| <= |scores|*(V+L) << 2^24)
    vrow = (colsf * gap)[None, :] + jnp.zeros((b, 1), jnp.float32)

    zero_b = jnp.zeros_like(nrows)          # batch-varying seed
    ring_init = jnp.full((b, k, l + 1), neg, jnp.float32) \
        + zero_b[:, None, None]
    best_init = (jnp.full((b,), neg, jnp.float32) + zero_b,
                 jnp.zeros((b,), jnp.int32) + zero_b)

    def step(carry, r):
        ring, best_score, best_row = carry
        pidx = preds[:, r - 1, :].astype(jnp.int32)        # [B, P]
        # per-lane pred-row pick as a gather along the ring axis; unlike
        # a one-hot matmul this scales ~flat in P and K (measured: p=16
        # k=128 costs +12% vs p=8 k=64, where the einsum cost 3.2x)
        slot = (pidx - 1) & (k - 1)
        gathered = jnp.take_along_axis(ring, slot[:, :, None], axis=1)
        hp = jnp.where((pidx > 0)[:, :, None], gathered,
                       jnp.where((pidx == 0)[:, :, None],
                                 vrow[:, None, :], neg))
        base_r = bases[:, r - 1]
        sub = jnp.where(seq == base_r[:, None], match,
                        mismatch).astype(jnp.float32)       # [B, L]
        diag_c = hp[:, :, :-1] + sub[:, None, :]            # [B,P,L]
        vert_c = hp + gap                                   # [B,P,L+1]
        diag_full = jnp.concatenate(
            [jnp.full((b, p, 1), neg, jnp.float32), diag_c], axis=2)
        t_best = jnp.maximum(jnp.max(diag_full, axis=1),
                             jnp.max(vert_c, axis=1))       # [B, L+1]
        # close the in-row gap chain: H[r][j] = max_{k<=j} T[k]+(j-k)g
        shifted = t_best - colsf * gap
        hr = lax.associative_scan(jnp.maximum, shifted,
                                  axis=1) + colsf * gap
        # direction codes with preference diag(p) < vert(p) < horiz,
        # recomputed against the final row value (always achievable)
        horiz = jnp.concatenate(
            [jnp.full((b, 1), neg, jnp.float32), hr[:, :-1] + gap],
            axis=1)
        cand = jnp.concatenate(
            [diag_full, vert_c, horiz[:, None, :]], axis=1)  # [B,2P+1,L+1]
        dirs = jnp.argmax(cand == hr[:, None, :],
                          axis=1).astype(jnp.uint8)
        ring = lax.dynamic_update_slice(
            ring, hr[:, None, :], (0, (r - 1) & (k - 1), 0))
        # fold sink-row end scores (earliest rank wins ties via strict >)
        is_sink = (sinks[:, r - 1] > 0) & (r <= nrows)
        s_r = hr[lanes, slen]
        better = is_sink & (s_r > best_score)
        best_score = jnp.where(better, s_r, best_score)
        best_row = jnp.where(better, r, best_row)
        return (ring, best_score, best_row), dirs

    (_, _, best_row), dir_rows = lax.scan(
        step, (ring_init,) + best_init,
        jnp.arange(1, v + 1, dtype=jnp.int32), unroll=_unroll(1))
    # dir_rows: [V, B, L+1] for ranks 1..V

    def tb_step(carry, _):
        r, j = carry
        done = (r == 0) & (j == 0)
        code = dir_rows[r - 1, lanes, j].astype(jnp.int32)
        is_diag = (code < p) & (r > 0)
        is_vert = (code >= p) & (code < 2 * p) & (r > 0)
        # r == 0 (virtual row) or horiz code: consume a seq char
        slot = jnp.where(is_diag, code, code - p)
        slot = jnp.clip(slot, 0, p - 1)
        pred_r = preds[lanes, jnp.maximum(r - 1, 0), slot].astype(
            jnp.int32)
        node = jnp.where(is_diag | is_vert, r - 1, PATH_NONE)
        spos = jnp.where(is_vert, PATH_NONE, j - 1)
        node = jnp.where(done, PATH_DONE, node)
        spos = jnp.where(done, PATH_DONE, spos)
        nr = jnp.where(is_diag | is_vert, pred_r, r)
        nj = jnp.where(is_vert, j, jnp.maximum(j - 1, 0))
        nr = jnp.where(done, r, nr)
        nj = jnp.where(done, j, nj)
        return (nr, nj), (node, spos)

    (_, _), (node_tape, seq_tape) = lax.scan(
        tb_step, (best_row.astype(jnp.int32), slen), None, length=v + l,
        unroll=_unroll(1))
    return jnp.transpose(node_tape), jnp.transpose(seq_tape)


@functools.partial(jax.jit,
                   static_argnums=(6, 7, 8, 9, 10, 11, 12, 13))
def _poa_kernel_banded(bases, preds, nrows, sinks, seq, slen,
                       v: int, l: int, p: int, k: int, wb: int,
                       match: int, mismatch: int, gap: int):
    """Banded variant of :func:`_poa_kernel`.

    Same inputs/outputs, but each rank's DP row is restricted to a
    ``wb``-column band centred on the rank's expected sequence position
    ``r * slen / nrows`` (layers align near the graph diagonal; indel
    drift within a 500 bp window is far below wb/2).  The ring buffer,
    candidate tensors and direction tape all shrink from ``l+1`` to
    ``wb`` columns, which is what the round cost is bound by (HBM
    traffic).  Band starts are a deterministic function of (r, slen,
    nrows), so the traceback recomputes them instead of storing them.
    The CUDA analog is cudapoa's banded NW (reference:
    src/cuda/cudabatch.cpp:54-62 banded flag).

    TPU-critical detail: band starts are QUANTIZED to ``wb//4`` so that
    cross-band realignment (pred rows and the sequence slice) is a
    select over a handful of statically-shifted slices — per-element
    ``take_along_axis`` gathers on the lane dimension are ~14x slower
    than the whole unbanded row DP (measured on v5e).
    """
    b = bases.shape[0]
    q = wb // 4                       # band-start quantum
    n_shift = 5                       # pred rows can lag <= 4 quanta
    cols = jnp.arange(wb, dtype=jnp.int32)
    colsf = cols.astype(jnp.float32)
    lanes = jnp.arange(b)
    neg = jnp.float32(-_BIG)
    nr = jnp.maximum(nrows, 1)
    # max band start in quanta: CEIL so the top band still reaches
    # column slen (s_max*q >= slen+1-wb; and s_max*q <= slen since
    # q <= wb), keeping the alignment endpoint inside the band
    smax_q = (jnp.maximum(slen + 1 - wb, 0) + q - 1) // q

    def band_start_q(r):
        """Quantized band start (in units of q) for rank(s) r ([B] or
        scalar), clamped so rank nrows can reach column slen (the
        alignment endpoint)."""
        c = ((r * slen) // nr - (wb // 2)) // q
        return jnp.clip(c, 0, smax_q)

    # per-lane sequence slices at every quantized start, precomputed
    # once: seq_sl[m][b, c] = seq[b, m*q + c - 1] (static slices)
    n_seq_sl = (max(0, l + 1 - wb) + q - 1) // q + 1
    seq_padl = jnp.concatenate(
        [jnp.zeros((b, 1), jnp.uint8), seq,
         jnp.zeros((b, wb), jnp.uint8)], axis=1)
    seq_sl = jnp.stack([seq_padl[:, m * q: m * q + wb]
                        for m in range(n_seq_sl)])   # [M, B, wb]

    zero_f = jnp.zeros_like(nrows).astype(jnp.float32)
    ring_init = jnp.full((b, k, wb), neg, jnp.float32) \
        + zero_f[:, None, None]
    best_init = (jnp.full((b,), neg, jnp.float32) + zero_f,
                 jnp.zeros((b,), jnp.int32) + jnp.zeros_like(nrows))

    def step(carry, r):
        ring, best_score, best_row = carry
        sq_r = band_start_q(r)                           # [B] (units q)
        s_r = sq_r * q
        pidx = preds[:, r - 1, :].astype(jnp.int32)      # [B, P]
        slot = (pidx - 1) & (k - 1)
        g1 = jnp.take_along_axis(ring, slot[:, :, None], axis=1)
        # realign pred rows (stored from their own band starts) to this
        # rank's band: hp_ext[c] = H_pred[s_r + c - 1], c in [0, wb].
        # delta is a whole number of quanta, so the realignment is a
        # select over n_shift statically-shifted slices of g1.
        sq_p = jnp.clip(
            ((pidx * slen[:, None]) // nr[:, None] - (wb // 2)) // q,
            0, smax_q[:, None])                          # [B, P]
        dq = sq_r[:, None] - sq_p                        # [B, P] >= 0
        g1_pad = jnp.concatenate(
            [jnp.full((b, p, 1), neg, jnp.float32), g1,
             jnp.full((b, p, n_shift * q), neg, jnp.float32)], axis=2)
        hp_ext = jnp.full((b, p, wb + 1), neg, jnp.float32)
        for m in range(n_shift):
            # slice m: H_pred values at columns s_p + m*q + c - 1
            hp_ext = jnp.where((dq == m)[:, :, None],
                               g1_pad[:, :, m * q: m * q + wb + 1],
                               hp_ext)
        j_ext = s_r[:, None] + jnp.arange(wb + 1,
                                          dtype=jnp.int32)[None, :] - 1
        vv = jnp.where(j_ext >= 0, j_ext.astype(jnp.float32) * gap,
                       neg)                              # virtual row
        hp_ext = jnp.where((pidx > 0)[:, :, None], hp_ext,
                           jnp.where((pidx == 0)[:, :, None],
                                     vv[:, None, :], neg))
        base_r = bases[:, r - 1]
        # sequence chars for this band: select the precomputed slice
        sb = seq_sl[0]
        for m in range(1, n_seq_sl):
            sb = jnp.where((sq_r == m)[:, None], seq_sl[m], sb)
        j_sub = s_r[:, None] + cols[None, :] - 1         # seq index
        sub_ok = (j_sub >= 0) & (j_sub < slen[:, None]) \
            & (sb == base_r[:, None])
        sub = jnp.where(sub_ok, match, mismatch).astype(jnp.float32)
        diag_c = hp_ext[:, :, :wb] + sub[:, None, :]     # [B, P, wb]
        vert_c = hp_ext[:, :, 1:] + gap                  # [B, P, wb]
        t_best = jnp.maximum(jnp.max(diag_c, axis=1),
                             jnp.max(vert_c, axis=1))    # [B, wb]
        shifted = t_best - colsf * gap
        hr = lax.associative_scan(jnp.maximum, shifted,
                                  axis=1) + colsf * gap
        horiz = jnp.concatenate(
            [jnp.full((b, 1), neg, jnp.float32), hr[:, :-1] + gap],
            axis=1)
        cand = jnp.concatenate(
            [diag_c, vert_c, horiz[:, None, :]], axis=1)  # [B,2P+1,wb]
        dirs = jnp.argmax(cand == hr[:, None, :],
                          axis=1).astype(jnp.uint8)
        ring = lax.dynamic_update_slice(
            ring, hr[:, None, :], (0, (r - 1) & (k - 1), 0))
        is_sink = (sinks[:, r - 1] > 0) & (r <= nrows)
        c_end = slen - s_r
        s_end = jnp.take_along_axis(
            hr, jnp.clip(c_end, 0, wb - 1)[:, None], axis=1)[:, 0]
        better = is_sink & (c_end < wb) & (s_end > best_score)
        best_score = jnp.where(better, s_end, best_score)
        best_row = jnp.where(better, r, best_row)
        return (ring, best_score, best_row), dirs

    (_, _, best_row), dir_rows = lax.scan(
        step, (ring_init,) + best_init,
        jnp.arange(1, v + 1, dtype=jnp.int32), unroll=_unroll(1))
    # dir_rows: [V, B, wb] for ranks 1..V

    def tb_step(carry, _):
        r, j = carry
        done = (r == 0) & (j == 0)
        c = jnp.clip(j - band_start_q(r) * q, 0, wb - 1)
        code = dir_rows[jnp.maximum(r - 1, 0), lanes, c].astype(
            jnp.int32)
        is_diag = (code < p) & (r > 0)
        is_vert = (code >= p) & (code < 2 * p) & (r > 0)
        slot = jnp.where(is_diag, code, code - p)
        slot = jnp.clip(slot, 0, p - 1)
        pred_r = preds[lanes, jnp.maximum(r - 1, 0), slot].astype(
            jnp.int32)
        node = jnp.where(is_diag | is_vert, r - 1, PATH_NONE)
        spos = jnp.where(is_vert, PATH_NONE, j - 1)
        node = jnp.where(done, PATH_DONE, node)
        spos = jnp.where(done, PATH_DONE, spos)
        nr_ = jnp.where(is_diag | is_vert, pred_r, r)
        nj = jnp.where(is_vert, j, jnp.maximum(j - 1, 0))
        nr_ = jnp.where(done, r, nr_)
        nj = jnp.where(done, j, nj)
        return (nr_, nj), (node, spos)

    (_, _), (node_tape, seq_tape) = lax.scan(
        tb_step, (best_row.astype(jnp.int32), slen), None, length=v + l,
        unroll=_unroll(1))
    return jnp.transpose(node_tape), jnp.transpose(seq_tape)


class _NativeBatch:
    """ctypes wrapper over the poa_batch.cpp lockstep API."""

    _bound = False

    @classmethod
    def _bind(cls):
        lib = cpu_ops.get_library()
        if not cls._bound:
            i8p = ctypes.POINTER(ctypes.c_uint8)
            lib.rt_poab_create.restype = ctypes.c_void_p
            lib.rt_poab_create.argtypes = [ctypes.c_int32]
            lib.rt_poab_destroy.argtypes = [ctypes.c_void_p]
            lib.rt_poab_seed.argtypes = [
                ctypes.c_void_p, ctypes.c_int32, ctypes.c_char_p,
                ctypes.c_int32, ctypes.c_char_p, ctypes.c_uint8]
            lib.rt_poab_export.restype = ctypes.c_int32
            lib.rt_poab_export.argtypes = [
                ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_int32,
                np.ctypeslib.ndpointer(np.uint8),
                np.ctypeslib.ndpointer(np.int16),
                np.ctypeslib.ndpointer(np.uint8),
                np.ctypeslib.ndpointer(np.int32)]
            lib.rt_poab_apply.argtypes = [
                ctypes.c_void_p, ctypes.c_int32,
                np.ctypeslib.ndpointer(np.int32),
                np.ctypeslib.ndpointer(np.int32),
                ctypes.c_int32, ctypes.c_char_p, ctypes.c_int32,
                ctypes.c_char_p, ctypes.c_uint8, ctypes.c_int32]
            lib.rt_poab_num_nodes.restype = ctypes.c_int32
            lib.rt_poab_num_nodes.argtypes = [ctypes.c_void_p,
                                              ctypes.c_int32]
            lib.rt_poab_consensus.restype = ctypes.c_int64
            lib.rt_poab_consensus.argtypes = [
                ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_char_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int32)]
            cls._bound = True
        return lib

    def __init__(self, n_windows: int):
        self.lib = self._bind()
        self.handle = ctypes.c_void_p(
            self.lib.rt_poab_create(n_windows))

    def close(self):
        if self.handle:
            self.lib.rt_poab_destroy(self.handle)
            self.handle = None

    def __del__(self):
        self.close()


class TPUPoaBatchEngine:
    """Lockstep batched POA over a megabatch of windows.

    Caps (vcap/pcap/lcap/max_depth) mirror the CUDA batch limits
    (max nodes per graph, max sequences per POA = 200,
    src/cuda/cudapolisher.cpp:229).
    """

    def __init__(self, match: int, mismatch: int, gap: int,
                 vcap: int = 2048, pcap: int = 16, lcap: int = 1024,
                 kcap: int = 128, max_depth: int = 200,
                 banded: bool = False, mesh=None):
        self.match, self.mismatch, self.gap = match, mismatch, gap
        self.vcap, self.pcap, self.lcap = vcap, pcap, lcap
        self.kcap = kcap
        self.max_depth = max_depth
        # banded (-b): halve the auto quarter-of-bucket DP band
        # (cudapoa banded analog, cudabatch.cpp:54-62); see
        # racon_tpu.utils.tuning.poa_band_cols for the 256 floor
        self.banded = banded
        self.cells = 0
        # mesh: shard each round's batch axis over the devices
        # (reference analog: per-device POA batch queues,
        # src/cuda/cudapolisher.cpp:231-243)
        self.mesh = mesh
        self.n_skipped_layers = 0
        # rejection observability: export failure code -> count
        # (-1 vcap, -2 pcap, -3 kcap; reference analog: the per-entry
        # status counters in cudabatch.cpp:136-155); guarded by a lock
        # because export() runs on the polisher's thread pool
        self.reject_counts = {-1: 0, -2: 0, -3: 0}
        self._reject_lock = threading.Lock()
        # per-phase wall accounting (cumulative over rounds):
        # export/apply are host C++ graph work, dispatch is the blocking
        # device step, extract is final consensus generation
        self.phase_walls = {"export": 0.0, "dispatch": 0.0,
                            "apply": 0.0, "extract": 0.0}
        # host-independent cumulative device time (watcher-thread
        # spans from poa_pallas.poa_full_dispatch; 0.0 on the
        # lockstep path, which has no async dispatch to watch)
        self.device_s = 0.0
        self.n_rounds = 0

    def consensus_batch(self, windows, trim: bool, pool=None) \
            -> List[Tuple[Optional[bytes], bool]]:
        """Polish a batch of Window objects on device (blocking).

        Returns one (consensus, polished) pair per window; consensus is
        None when the window overflowed the device caps and must be
        re-polished on the CPU (reference: cudapolisher.cpp:357-386).
        """
        return self.consensus_batch_async(windows, trim, pool)()

    def consensus_batch_async(self, windows, trim: bool, pool=None):
        """Dispatch a batch and return a zero-arg collect closure.

        On a TPU backend (or with Pallas interpret mode forced) the
        whole POA runs inside ONE Pallas dispatch
        (racon_tpu/tpu/poa_pallas.py, the cudapoa-shaped design),
        sharded over the mesh batch axis when the mesh has more than
        one device, and the dispatch returns BEFORE the device
        finishes -- callers can pack/dispatch the next megabatch while
        this one computes (upload + host packing overlap device time).
        Otherwise the portable lockstep lax.scan engine runs
        synchronously and the closure just returns its results.
        """
        from racon_tpu.tpu import poa_pallas
        if self.will_dispatch_async(windows):
            # the kernel's window type is a compile-time constant;
            # split mixed batches so each window trims per its own
            # type (parity with the per-window lockstep/CPU paths).
            # _fits_full_device rejects configurations that exceed the
            # kernel's VMEM budget -> lockstep below.
            types = {w.type.value for w in windows}
            if len(types) <= 1:
                return self._run_full_device_async(windows, trim)
            collects = []
            for tv in sorted(types):
                idxs = [i for i, w in enumerate(windows)
                        if w.type.value == tv]
                collects.append(
                    (idxs, self._run_full_device_async(
                        [windows[i] for i in idxs], trim)))

            def collect_mixed():
                results: List[Tuple[Optional[bytes], bool]] = \
                    [None] * len(windows)
                for idxs, coll in collects:
                    for i, r in zip(idxs, coll()):
                        results[i] = r
                return results

            return collect_mixed
        n = len(windows)

        def run_lockstep():
            nb = _NativeBatch(n)
            try:
                return self._run(nb, windows, trim, pool)
            finally:
                nb.close()

        # lockstep runs synchronously at dispatch time; its interval
        # IS the engine-busy window on backends without the Pallas
        # kernel (the watcher threads never run there)
        t0 = _mono()
        out = run_lockstep()
        obs_devutil.DEVICE_UTIL.record("poa", t0, _mono())
        return lambda: out

    # -- full on-device path (flagship Pallas kernel) ------------------

    def will_dispatch_async(self, windows) -> bool:
        """True when ``consensus_batch_async`` would return before the
        device finishes (the full-device Pallas path); the lockstep
        fallback runs synchronously at dispatch time, so pipelining
        callers must not attribute its wall to an in-flight batch."""
        from racon_tpu.tpu import poa_pallas
        return poa_pallas.available() and \
            self._fits_full_device(windows)

    def _fits_full_device(self, windows) -> bool:
        """Side-effect-free VMEM precheck (d1 from raw layer counts,
        an upper bound on what _order_layers keeps)."""
        from racon_tpu.tpu import poa_pallas
        from racon_tpu.utils.tuning import pow2_at_least

        lp = self.lcap
        wb = poa_pallas.band_width(lp, self.banded)
        depth = max((min(len(w.sequences) - 1, self.max_depth)
                     for w in windows), default=0)
        d1 = max(8, pow2_at_least(depth + 1, 8))
        return poa_pallas.fits(self.vcap, lp, d1, self.pcap,
                               self.pcap, 8, wb)

    def _order_layers(self, w):
        idx = sorted(range(1, len(w.sequences)),
                     key=lambda i: w.positions[i][0])
        kept = [i for i in idx
                if len(w.sequences[i]) <= self.lcap][:self.max_depth]
        self.n_skipped_layers += len(idx) - len(kept)
        return kept

    def _run_full_device_async(self, windows, trim):
        """Dispatch one megabatch; returns a zero-arg collect closure.
        Callers must have passed _fits_full_device first."""
        from racon_tpu.tpu import poa_pallas
        from racon_tpu.utils.tuning import pow2_at_least

        # <3-sequence windows keep the backbone verbatim (reference:
        # cudabatch.cpp:214-222) -- short-circuit them before packing
        # so they cost no device work or d1/b_pad head-room
        if any(len(w.sequences) < 3 for w in windows):
            out: List[Tuple[Optional[bytes], bool]] = \
                [None] * len(windows)
            dev_idx = []
            for i, w in enumerate(windows):
                if len(w.sequences) < 3:
                    out[i] = (w.sequences[0], False)
                else:
                    dev_idx.append(i)
            sub = self._run_full_device_async(
                [windows[i] for i in dev_idx], trim) if dev_idx \
                else None

            def collect_shortcut():
                if sub is not None:
                    for i, r in zip(dev_idx, sub()):
                        out[i] = r
                return out

            return collect_shortcut

        n = len(windows)
        layer_lists = [self._order_layers(w) for w in windows]
        v, lp = self.vcap, self.lcap
        # -b narrows the band; the on-device DP needs >= 256 columns
        # (quantum 128), so the narrow setting clamps up
        wb = poa_pallas.band_width(lp, self.banded)
        d1 = max(8, pow2_at_least(
            max((len(ll) for ll in layer_lists), default=0) + 1, 8))
        b_pad = max(8, pow2_at_least(n, 8))

        with obs_trace.span("racon_tpu.poa_pack", cat="poa") as sp:
            seqs = np.zeros((b_pad, d1, lp), np.uint8)
            wts = np.ones((b_pad, d1, lp), np.uint8)
            meta = np.zeros((b_pad, d1, 8), np.int32)
            nlay = np.zeros(b_pad, np.int32)
            bblen = np.ones(b_pad, np.int32)
            seqs[:, 0, 0] = ord("A")        # pad windows: 1-base backbone
            host_fail = [False] * n
            for b, w in enumerate(windows):
                bb = w.sequences[0]
                if len(bb) > min(lp, v):
                    host_fail[b] = True     # vcap analog, CPU re-polish
                    continue
                bblen[b] = len(bb)
                seqs[b, 0, :len(bb)] = np.frombuffer(bb, np.uint8)
                q0 = w.qualities[0]
                if q0:
                    wts[b, 0, :len(bb)] = \
                        np.frombuffer(q0, np.uint8).astype(np.int32) \
                        .clip(33, None).astype(np.uint8) - 33
                offset = int(0.01 * len(bb))
                nlay[b] = len(layer_lists[b])
                for d, li in enumerate(layer_lists[b], start=1):
                    s = w.sequences[li]
                    seqs[b, d, :len(s)] = np.frombuffer(s, np.uint8)
                    ql = w.qualities[li]
                    if ql:
                        wts[b, d, :len(s)] = \
                            np.frombuffer(ql, np.uint8).astype(np.int32) \
                            .clip(33, None).astype(np.uint8) - 33
                    begin, end = w.positions[li]
                    full = 1 if (begin < offset
                                 and end > len(bb) - offset) else 0
                    meta[b, d, :4] = (begin, end, full, len(s))
        with self._reject_lock:
            self.phase_walls["export"] += sp.seconds

        handle = poa_pallas.poa_full_dispatch(
            seqs, wts, meta, nlay, bblen, v=v, lp=lp, d1=d1,
            p=self.pcap, s=self.pcap, a=8, k=self.kcap, wb=wb,
            match=self.match, mismatch=self.mismatch, gap=self.gap,
            wtype=windows[0].type.value, trim=1 if trim else 0,
            mesh=self.mesh)

        def collect():
            with obs_trace.span("racon_tpu.poa_wait", cat="poa") as sp:
                cons, mout = handle()
            blocked = sp.seconds
            # NOTE under the double-buffered pipeline: "dispatch"
            # counts only the UN-overlapped blocking residual (device
            # time hidden behind the next batch's packing shows up in
            # no bucket), so phase walls no longer sum to the stage
            # wall; the watcher-thread span below is the
            # host-independent per-dispatch device time.  Counter
            # updates take the lock: the streaming pipeline
            # (racon_tpu/tpu/polisher.py) shares one engine between
            # the speculative align-stage consumer thread and the
            # stage-time dispatch loop
            dev_s = getattr(handle, "device_s", lambda: 0.0)()
            with self._reject_lock:
                self.phase_walls["dispatch"] += blocked
                self.device_s += dev_s
            if dev_s > 0:
                # per-megabatch device-time distribution (the engine
                # only keeps the aggregate; the serve-layer latency
                # percentiles want the shape)
                obs_metrics.REGISTRY.observe(
                    "poa_megabatch_device_s", dev_s)
            with self._reject_lock:
                self.n_rounds += 1
                self.cells += int(mout[:n, 4].sum()) * wb

            with obs_trace.span("racon_tpu.poa_extract", cat="poa") \
                    as sp:
                results: List[Tuple[Optional[bytes], bool]] = []
                code_map = {poa_pallas.FAIL_VCAP: -1,
                            poa_pallas.FAIL_EDGE: -2,
                            poa_pallas.FAIL_ALIGNED: -2,
                            poa_pallas.FAIL_KCAP: -3,
                            poa_pallas.FAIL_PATH: -3}
                for b, w in enumerate(windows):
                    length = int(mout[b, 0])
                    if host_fail[b] or length < 0:
                        code = code_map.get(int(mout[b, 2]), -1)
                        with self._reject_lock:
                            self.reject_counts[code] = \
                                self.reject_counts.get(code, 0) + 1
                        obs_decision.DECISIONS.record("poa_reject", code=code,
                                         phase="extract")
                        results.append((None, False))
                        continue
                    if int(mout[b, 1]) == 2:
                        w.warn_chimeric()
                    results.append(
                        (bytes(cons[b, :length].astype(np.uint8)), True))
            with self._reject_lock:
                self.phase_walls["extract"] += sp.seconds
            return results

        return collect

    # -- helpers -------------------------------------------------------

    def _run(self, nb, windows, trim, pool):
        lib, handle = nb.lib, nb.handle
        n = len(windows)
        layer_lists = [self._order_layers(w) for w in windows]

        def seed(i):
            w = windows[i]
            backbone = w.sequences[0]
            qual = w.qualities[0]
            lib.rt_poab_seed(handle, i, backbone, len(backbone),
                             qual if qual else b"\x00" * len(backbone),
                             1 if qual else 0)

        _map(pool, seed, range(n))

        failed = [False] * n
        max_rounds = max((len(ll) for ll in layer_lists), default=0)

        v, l, p = self.vcap, self.lcap, self.pcap
        bases = np.zeros((n, v), dtype=np.uint8)
        preds = np.full((n, v, p), -1, dtype=np.int16)
        sinks = np.zeros((n, v), dtype=np.uint8)
        rank2node = np.zeros((n, v), dtype=np.int32)
        nrows = np.zeros(n, dtype=np.int32)
        seq_arr = np.zeros((n, l), dtype=np.uint8)
        slen = np.zeros(n, dtype=np.int32)

        for d in range(max_rounds):
            active = [i for i in range(n)
                      if not failed[i] and d < len(layer_lists[i])]
            if not active:
                break
            nrows[:] = 0
            slen[:] = 0

            def export(i):
                w = windows[i]
                li = layer_lists[i][d]
                begin, end = w.positions[li]
                blen = len(w.sequences[0])
                offset = int(0.01 * blen)
                full = begin < offset and end > blen - offset
                rows = lib.rt_poab_export(
                    handle, i, begin, end, 1 if full else 0, v, p,
                    self.kcap, bases[i], preds[i].reshape(-1),
                    sinks[i], rank2node[i])
                if rows < 0:
                    failed[i] = True
                    with self._reject_lock:
                        self.reject_counts[rows] = \
                            self.reject_counts.get(rows, 0) + 1
                    obs_decision.DECISIONS.record("poa_reject", code=int(rows),
                                     phase="export")
                    return
                nrows[i] = rows
                s = w.sequences[li]
                seq_arr[i, :len(s)] = np.frombuffer(s, dtype=np.uint8)
                slen[i] = len(s)

            with obs_trace.span("racon_tpu.poa_pack", cat="poa") as sp:
                _map(pool, export, active)
            self.phase_walls["export"] += sp.seconds
            active = [i for i in active if not failed[i]]
            if not active:
                continue

            # NOTE: no active-lane compaction — the rank scan's cost is
            # per-step overhead x steps, independent of batch width
            # (measured: compacting tail rounds to 32 lanes saved
            # nothing and the extra compiled shapes cost ~5s), so idle
            # lanes in late rounds ride along for free
            with obs_trace.span("racon_tpu.poa_wait", cat="poa") as sp:
                node_tape, seq_tape = self._dispatch(
                    bases, preds, nrows, sinks, seq_arr, slen)
            self.phase_walls["dispatch"] += sp.seconds
            self.n_rounds += 1

            def apply(i):
                w = windows[i]
                li = layer_lists[i][d]
                nt, st = node_tape[i], seq_tape[i]
                k = int(np.argmax(nt == PATH_DONE)) \
                    if (nt == PATH_DONE).any() else nt.shape[0]
                # reversed tape -> forward path; translate ranks -> ids
                pn = nt[:k][::-1].astype(np.int32)
                ps = st[:k][::-1].astype(np.int32)
                mask = pn >= 0
                pn = np.where(mask, rank2node[i][np.clip(pn, 0, None)],
                              PATH_NONE)
                pn = np.ascontiguousarray(pn)
                ps = np.ascontiguousarray(ps)
                s = w.sequences[li]
                q = w.qualities[li]
                lib.rt_poab_apply(
                    handle, i, pn, ps, len(pn), s, len(s),
                    q if q else b"\x00" * len(s), 1 if q else 0,
                    int(w.positions[li][0]))

            t0 = _mono()
            _map(pool, apply, active)
            self.phase_walls["apply"] += _mono() - t0

        # consensus extraction (pooled; the native call releases the GIL)
        results: List[Tuple[Optional[bytes], bool]] = [None] * n
        out_cap = 4 * self.lcap + 4096

        def extract(i):
            if failed[i]:
                results[i] = (None, False)
                return
            # gate on the RAW window sequence count, like the reference
            # (cudabatch.cpp:214-222): layers skipped for length/depth
            # only reduce coverage, they do not demote the window
            if len(windows[i].sequences) < 3:
                # <3 sequences -> backbone verbatim, unpolished
                # (reference: cudabatch.cpp:214-222, window.cpp:68-71)
                results[i] = (windows[i].sequences[0], False)
                return
            out = ctypes.create_string_buffer(out_cap)
            status = ctypes.c_int32(0)
            length = lib.rt_poab_consensus(
                handle, i, windows[i].type.value, 1 if trim else 0,
                out, out_cap, ctypes.byref(status))
            if length < 0:
                results[i] = (None, False)
                return
            if status.value == 2:
                windows[i].warn_chimeric()
            results[i] = (out.raw[:length], True)

        with obs_trace.span("racon_tpu.poa_extract", cat="poa") as sp:
            _map(pool, extract, range(n))
        self.phase_walls["extract"] += sp.seconds
        return results

    @staticmethod
    def _pow2(n: int, lo: int) -> int:
        from racon_tpu.utils.tuning import pow2_at_least
        return pow2_at_least(n, lo)

    def _band_cols(self, l_b: int) -> int:
        """Effective band width for layer bucket ``l_b`` (0 = unbanded:
        the band would cover the whole row anyway)."""
        return poa_band_cols(l_b, self.banded)

    def _dispatch(self, bases, preds, nrows, sinks, seq_arr, slen):
        # bucket this round's static dims to the active maxima so scan
        # length tracks real graph sizes, not the worst-case caps
        v_b = min(self._pow2(int(nrows.max()), 128), self.vcap)
        l_b = min(self._pow2(int(slen.max()), 128), self.lcap)
        wb = self._band_cols(l_b)
        self.cells += bases.shape[0] * v_b * (wb if wb else l_b + 1)
        args = (bases[:, :v_b], preds[:, :v_b, :], nrows,
                sinks[:, :v_b], seq_arr[:, :l_b], slen)
        n_dev = len(self.mesh.devices) if self.mesh is not None else 1
        if n_dev > 1:
            from racon_tpu.parallel import mesh_utils
            args = [mesh_utils.pad_to_multiple(np.ascontiguousarray(a),
                                               n_dev, 0)
                    for a in args]
            node_tape, seq_tape = mesh_utils.sharded_poa(
                self.mesh, *args, v=v_b, l=l_b, p=self.pcap,
                k=self.kcap, wb=wb, match=self.match,
                mismatch=self.mismatch, gap=self.gap)
            b = bases.shape[0]
            return np.asarray(node_tape)[:b], np.asarray(seq_tape)[:b]
        if wb:
            node_tape, seq_tape = _poa_kernel_banded(
                *(jnp.asarray(a) for a in args), v_b, l_b, self.pcap,
                self.kcap, wb, self.match, self.mismatch, self.gap)
        else:
            node_tape, seq_tape = _poa_kernel(
                *(jnp.asarray(a) for a in args), v_b, l_b, self.pcap,
                self.kcap, self.match, self.mismatch, self.gap)
        return np.asarray(node_tape), np.asarray(seq_tape)


def _map(pool, fn, items):
    if pool is None:
        for it in items:
            fn(it)
    else:
        list(pool.map(fn, items))
