"""Canonical content keys for the result cache (r18).

Every cacheable work unit is reduced to a fixed-size blake2b digest
over (a) the unit's canonical input bytes, (b) the full engine
configuration that shapes the computation, and (c) the engine-code
*epoch* — a fingerprint of the package version plus every resolved
``RACON_TPU_*`` knob that can influence output bytes.  Two units
share a key iff recomputing either would provably produce the same
output bytes, which is exactly the byte-determinism contract pinned
since PR 3: a hit is then indistinguishable from recomputation.

Key spaces are deliberately disjoint per compute path: the CPU POA
engine and the device POA pipeline resolve cost ties independently,
so ``poa_key`` takes a ``space`` tag ("cpu" / "dev") and the device
space additionally carries the engine-config tuple the PR 9 executor
fuses on (scoring, caps, banded flag, mesh — ``PoaEngineHandle.
cfg_key``).  Align keys carry the rung geometry (bucket dims, error
cap / band width), the per-pair empirical center when one is pinned,
and the mesh.

The epoch EXCLUDES knobs that are proven output-neutral and vary
between otherwise-identical runs: the cache's own knobs (changing
the byte budget must never invalidate entries) and the pure
observability/durability planes (trace, flight, decisions, journal,
fleet scraper — each pinned byte-identical on/off by its own tier-1
tests).  Everything else — kernel shapes, ladder caps, scoring,
split policy — is hashed, so any knob delta that COULD change bytes
changes every key.
"""

from __future__ import annotations

import hashlib
import struct

#: knobs that never affect output bytes (each pinned by tests) and
#: therefore stay OUT of the epoch fingerprint.  The cache's own
#: knobs lead the list: resizing the budget or toggling persistence
#: must not orphan every existing entry.
EPOCH_EXCLUDE = frozenset({
    "RACON_TPU_CACHE",
    "RACON_TPU_CACHE_MB",
    "RACON_TPU_CACHE_PERSIST",
    "RACON_TPU_CACHE_DIR",
    # observability planes (pinned byte-identical on/off)
    "RACON_TPU_TRACE",
    "RACON_TPU_METRICS_JSON",
    "RACON_TPU_FLIGHT",
    "RACON_TPU_FLIGHT_RING",
    "RACON_TPU_FLIGHT_DUMP",
    "RACON_TPU_DECISIONS",
    "RACON_TPU_DECISIONS_RING",
    "RACON_TPU_SERVE_SAMPLE_S",
    "RACON_TPU_BENCH_GATE",
    # durability + fleet planes (replay/scrape only)
    "RACON_TPU_JOURNAL",
    "RACON_TPU_JOURNAL_DIR",
    "RACON_TPU_JOURNAL_FSYNC",
    "RACON_TPU_FAULT",
    "RACON_TPU_FLEET_INTERVAL_S",
    "RACON_TPU_FLEET_TIMEOUT_S",
    "RACON_TPU_FLEET_STALE_S",
    # fleet router (r19): placement policy — which backend runs a
    # job never changes the job's bytes
    "RACON_TPU_ROUTE_PROBE_S",
    "RACON_TPU_ROUTE_PROBE_TIMEOUT_S",
    "RACON_TPU_ROUTE_BREAKER_FAILS",
    "RACON_TPU_ROUTE_BREAKER_COOLDOWN_S",
    "RACON_TPU_ROUTE_TCP",
    # scatter/gather (r20): shard count is placement policy, never a
    # bytes decision — the shard mask only changes WHICH targets a
    # process emits, and concatenation in shard order is pinned
    # byte-identical to the unsharded run (target_slice contract)
    "RACON_TPU_SCATTER_MIN_WALL_S",
    "RACON_TPU_SCATTER_MAX_SHARDS",
    # r21: staged parsing is pinned byte-identical to the full parse
    # (tests/test_fastio.py fuzz + tests/test_scatter.py), and the
    # straggler factor only moves WHERE a shard's attempt runs
    "RACON_TPU_STAGE",
    "RACON_TPU_SCATTER_REBALANCE",
    # r22 closed control loop: affinity routing moves WHERE a job
    # runs, the adaptive fusion window moves WHEN a bucket
    # dispatches, drift epochs move WHEN rates recalibrate (per-job
    # pins keep in-flight jobs on their admission snapshot), and the
    # class knobs move ordering/admission — all pinned byte-identical
    # on/off (tests/test_control.py)
    "RACON_TPU_ROUTE_AFFINITY",
    "RACON_TPU_FUSE_ADAPT",
    "RACON_TPU_CALIB_DRIFT_EPOCH",
    "RACON_TPU_CLASS_TARGET_P99_S",
    "RACON_TPU_CLASS_HEADROOM",
    # r24 internal mapping: ONLY the placement/pricing knobs.  The
    # mapper's k/w/occ/min-chain/band/max-gap knobs change which
    # overlaps exist (bytes!) and deliberately stay IN the epoch.
    "RACON_TPU_MAP_DEVICE_SEED",
    "RACON_TPU_SERVE_MAP_MBPS",
})

DIGEST_SIZE = 32


def engine_epoch() -> bytes:
    """Fingerprint of the code + knob environment results depend on.

    Cheap (one env sweep + one small hash) but not free — batch call
    sites fetch it once per submission and pass it to the per-unit
    key functions below.
    """
    import racon_tpu
    from racon_tpu.obs import provenance

    h = hashlib.blake2b(digest_size=16)
    h.update(racon_tpu.__version__.encode())
    for name, info in sorted(provenance.resolved_knobs().items()):
        if name in EPOCH_EXCLUDE:
            continue
        h.update(b"\0%s=%s" % (name.encode(), info["value"].encode()))
    return h.digest()


def _h(tag: bytes, epoch: bytes):
    h = hashlib.blake2b(digest_size=DIGEST_SIZE)
    h.update(tag)
    h.update(epoch)
    return h


def _as_bytes(seq) -> bytes:
    if isinstance(seq, bytes):
        return seq
    if isinstance(seq, (bytearray, memoryview)):
        return bytes(seq)
    import numpy as np

    a = np.ascontiguousarray(seq)
    return a.dtype.str.encode() + a.tobytes()


def window_digest(window) -> bytes:
    """Canonical content digest of one Window: type + every layer's
    (sequence, quality, begin, end) in insertion order — which the
    WindowLedger already pins to overlap-ordinal order, so streamed
    and staged builds of the same window digest identically."""
    h = hashlib.blake2b(digest_size=DIGEST_SIZE)
    h.update(b"win1|%d|%d" % (int(window.type.value),
                              len(window.sequences)))
    for i, seq in enumerate(window.sequences):
        qual = window.qualities[i]
        begin, end = window.positions[i]
        h.update(struct.pack("<IIIi", len(seq),
                             len(qual) if qual else 0,
                             int(begin), int(end)))
        h.update(seq)
        if qual:
            h.update(qual)
    return h.digest()


def poa_key(space: str, cfg_key, trim: bool, window,
            epoch: bytes) -> bytes:
    """One POA window unit.  ``space`` separates the CPU engine from
    the device pipeline (distinct tie-breaking); ``cfg_key`` is the
    full engine-config tuple (the executor's fuse/engine key for the
    device space, (match, mismatch, gap) for the CPU engine)."""
    h = _h(b"poa|", epoch)
    h.update(space.encode())
    h.update(repr(cfg_key).encode())
    h.update(b"|t%d|" % int(bool(trim)))
    h.update(window_digest(window))
    return h.digest()


def wfa_key(query, target, lq: int, emax: int, mesh_key,
            epoch: bytes) -> bytes:
    """One WFA align pair: pair bytes + rung geometry (bucket dim,
    error cap) + mesh."""
    h = _h(b"wfa|", epoch)
    h.update(repr((int(lq), int(emax), mesh_key)).encode())
    q = _as_bytes(query)
    h.update(struct.pack("<I", len(q)))
    h.update(q)
    h.update(_as_bytes(target))
    return h.digest()


def band_key(query, target, lq: int, lt: int, wb: int, center,
             mesh_key, epoch: bytes) -> bytes:
    """One banded align pair: pair bytes + rung geometry (bucket
    dims, band width), the per-pair empirical center path when one
    is pinned, and the mesh."""
    h = _h(b"band|", epoch)
    h.update(repr((int(lq), int(lt), int(wb), mesh_key)).encode())
    if center is None:
        h.update(b"c0|")
    else:
        c = _as_bytes(center)
        h.update(b"c1|" + struct.pack("<I", len(c)))
        h.update(c)
    q = _as_bytes(query)
    h.update(struct.pack("<I", len(q)))
    h.update(q)
    h.update(_as_bytes(target))
    return h.digest()


def scan_key(query, target, blq: int, blt: int, need_ratio,
             epoch: bytes) -> bytes:
    """One CPU scan-ladder pair (band_align_batch): the ladder's
    per-pair result depends only on the pair bytes, the bucket dims
    and the probe need ratio — chunking and the memory budget only
    batch, they never change a lane's answer."""
    h = _h(b"scan|", epoch)
    h.update(repr((int(blq), int(blt),
                   round(float(need_ratio), 9))).encode())
    q = _as_bytes(query)
    h.update(struct.pack("<I", len(q)))
    h.update(q)
    h.update(_as_bytes(target))
    return h.digest()
