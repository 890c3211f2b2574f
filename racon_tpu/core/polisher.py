"""Polisher: the pipeline orchestrator (reference: src/polisher.{hpp,cpp}).

Drives parse -> overlap filtering -> breaking points -> windowing ->
batched consensus -> stitching.  The accelerator seam is the same as the
reference's (src/polisher.hpp:55,74): two overridable methods,
``find_overlap_breaking_points`` and ``generate_consensuses``; the
TPUPolisher subclass (racon_tpu.tpu.polisher) overrides both to run the
batched device kernels with CPU fallback for whatever the device path
rejects, exactly like CUDAPolisher (src/cuda/cudapolisher.cpp).
"""

from __future__ import annotations

import concurrent.futures
import enum
from typing import Dict, List, Optional

from racon_tpu.core import overlap as overlap_mod
from racon_tpu.core.overlap import InvalidInputError, Overlap
from racon_tpu.core.sequence import Sequence
from racon_tpu.core.window import Window, WindowType
from racon_tpu.io.parsers import (create_overlap_parser,
                                  create_sequence_parser)
from racon_tpu.obs import REGISTRY, Registry
from racon_tpu.obs import calhealth as obs_calhealth
from racon_tpu.obs import trace as obs_trace
from racon_tpu.ops import cpu
from racon_tpu.utils.logger import Logger

CHUNK_SIZE = 1024 * 1024 * 1024  # reference kChunkSize (polisher.cpp:26)


class JobCanceledError(RuntimeError):
    """The serve tier canceled this job (r21 straggler rebalancing:
    the router superseded a slow shard with a replacement attempt and
    sent best-effort ``cancel`` to the original).  Raised from the
    polisher's cancel poll sites — always BETWEEN committed units, so
    a canceled job's journal/checkpoint state stays consistent."""


class PolisherType(enum.Enum):
    kC = 0  # contig polishing
    kF = 1  # fragment (read) error correction


def create_polisher(sequences_path: str, overlaps_path: Optional[str],
                    target_path: str, type_: PolisherType,
                    window_length: int, quality_threshold: float,
                    error_threshold: float, trim: bool, match: int,
                    mismatch: int, gap: int, num_threads: int,
                    tpu_poa_batches: int = 0,
                    tpu_banded_alignment: bool = False,
                    tpu_aligner_batches: int = 0) -> "Polisher":
    """Factory mirroring racon::createPolisher (src/polisher.cpp:55-159).

    TPU offload is selected per stage by ``tpu_poa_batches`` /
    ``tpu_aligner_batches`` the same way the reference gates CUDA
    offload by --cudapoa-batches / --cudaaligner-batches.

    ``overlaps_path=None`` (r24) selects internal overlap discovery:
    instead of parsing a PAF/MHAP/SAM file, initialize() maps the
    reads against the targets with the built-in minimap-lite mapper
    (racon_tpu/overlap) and feeds the discovered overlaps through the
    exact same filter/align path.
    """
    if not isinstance(type_, PolisherType):
        raise InvalidInputError("invalid polisher type!")
    if window_length == 0:
        raise InvalidInputError("invalid window length!")

    sparser = create_sequence_parser(sequences_path)
    oparser = (create_overlap_parser(overlaps_path)
               if overlaps_path is not None else None)
    tparser = create_sequence_parser(target_path)

    if tpu_poa_batches > 0 or tpu_aligner_batches > 0:
        try:
            from racon_tpu.tpu.polisher import TPUPolisher
        except ImportError as exc:
            raise InvalidInputError(
                f"TPU support is not available ({exc})") from exc
        return TPUPolisher(sparser, oparser, tparser, type_, window_length,
                           quality_threshold, error_threshold, trim, match,
                           mismatch, gap, num_threads, tpu_poa_batches,
                           tpu_banded_alignment, tpu_aligner_batches)
    return Polisher(sparser, oparser, tparser, type_, window_length,
                    quality_threshold, error_threshold, trim, match,
                    mismatch, gap, num_threads)


class _MappedOverlapSource:
    """Parser-shaped view over internally discovered overlaps (r24).

    Lets ``_load_overlaps`` run its existing transmute/filter loop
    unchanged over in-memory mapper output: one chunk, then done.  No
    ``set_stage`` on purpose — staged-input plans describe file byte
    ranges and do not apply to mapped records."""

    def __init__(self, records: List[Overlap]):
        self._records = records
        self._done = False

    def reset(self) -> None:
        self._done = False

    def close(self) -> None:
        self._records = []

    def parse(self, dst: List[Overlap], max_bytes: int) -> bool:
        if not self._done:
            dst.extend(self._records)
            self._done = True
        return False


class Polisher:
    def __init__(self, sparser, oparser, tparser, type_: PolisherType,
                 window_length: int, quality_threshold: float,
                 error_threshold: float, trim: bool, match: int,
                 mismatch: int, gap: int, num_threads: int):
        self.sparser = sparser
        self.oparser = oparser
        self.tparser = tparser
        self.type = type_
        self.window_length = window_length
        self.quality_threshold = quality_threshold
        self.error_threshold = error_threshold
        self.trim = trim
        self.match, self.mismatch, self.gap = match, mismatch, gap
        self.num_threads = max(1, num_threads)

        self.sequences: List[Sequence] = []
        self.windows: List[Window] = []
        self.targets_coverages: List[int] = []
        self._owned_targets = None   # multi-host target mask
        # r20 scatter: the serve tier sets (index, count) on a
        # target-sharded sub-job; initialize() turns it into the same
        # target_slice ownership mask the multi-host path uses
        self._target_shard = None
        # r21 serve seams (racon_tpu/serve/session.py wires both):
        # a staged-input hint shipped with a scattered sub-job
        # (spec["stage"] -> ranged overlap scan), and a cancel poll
        # the straggler rebalancer uses to stop a superseded original
        self._stage_hint = None
        self._cancel_check = None
        # streaming bookkeeping (racon_tpu/tpu/polisher.py pipeline):
        # window-id offsets per target, and whether the subclass
        # already counted per-target coverages at registration time
        self._first_window_id: List[int] = []
        self._targets_size = 0
        self._coverage_counted = False
        # r24 internal mapping: oparser None means initialize()
        # discovers overlaps with racon_tpu/overlap instead of
        # parsing a file; stats land here for reports/decisions
        self._map_stats: Optional[dict] = None
        # per-stage wall clocks surfaced in --metrics-json and the
        # serve report (the TPU subclass adds its device stages)
        self.stage_walls: Dict[str, float] = {}
        self.dummy_quality = b"!" * window_length
        # per-run metrics registry (racon_tpu/obs): every counter this
        # run records also propagates into the process-wide REGISTRY,
        # so bench.py reads per-polish numbers here and the CLI's
        # --metrics-json report is assembled from the same store
        self.metrics = Registry(parent=REGISTRY)
        self.engine = cpu.PoaEngine(match, mismatch, gap)
        self.logger = Logger()
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=self.num_threads)

    # ------------------------------------------------------------------
    # initialize: reference src/polisher.cpp:191-459
    # ------------------------------------------------------------------

    def initialize(self) -> None:
        if self.windows:
            print("[racon_tpu::Polisher::initialize] warning: object "
                  "already initialized!")
            return
        with obs_trace.span("racon_tpu.initialize", cat="stage"):
            self._initialize()

    def _initialize(self) -> None:
        self.logger.log()
        # run-wall anchor for the derived host.share gauge (obs clock;
        # records only, never feeds control flow)
        self._t_run_start = obs_trace.now()
        with obs_trace.span("racon_tpu.load_targets", cat="stage",
                            metric="host.parse_s",
                            registry=self.metrics):
            self.tparser.reset()
            self.tparser.parse(self.sequences, -1)
        targets_size = len(self.sequences)
        if targets_size == 0:
            raise InvalidInputError("empty target sequences set!")

        # multi-host scale-out: under jax.distributed each rank owns a
        # deterministic contiguous slice of the targets, builds
        # windows only for those, and emits only those (the wrapper
        # --split flow, cross-host; racon_tpu/parallel/multihost.py).
        # Ownership is a MASK, not a slice: every id mapping (MHAP's
        # order-based ids included) must see the full target set.
        from racon_tpu.parallel import multihost
        nproc, rank = multihost.maybe_initialize()
        self._owned_targets = None
        if nproc > 1:
            sl = multihost.target_slice(targets_size, nproc, rank)
            self._owned_targets = [sl.start <= i < sl.stop
                                   for i in range(targets_size)]
            self.logger.log(
                f"[racon_tpu::Polisher::initialize] multi-host rank "
                f"{rank}/{nproc}: targets [{sl.start}, {sl.stop})")
        # r20 scatter (racon_tpu/serve/scatter.py): a scattered
        # sub-job owns one target_slice shard of the full target set.
        # Reusing the multi-host mask means the shard's emitted bytes
        # are exactly the slice the `cat part*.fa` contract pins, so
        # concatenating shard outputs in index order reproduces the
        # unsharded run byte-for-byte.  A multi-host rank is never
        # also a serve shard (the mask above wins).
        if self._owned_targets is None and self._target_shard:
            index, count = self._target_shard
            sl = multihost.target_slice(targets_size, count, index)
            self._owned_targets = [sl.start <= i < sl.stop
                                   for i in range(targets_size)]
            self.logger.log(
                f"[racon_tpu::Polisher::initialize] target shard "
                f"{index}/{count}: targets [{sl.start}, {sl.stop})")

        name_to_id: Dict[str, int] = {}
        id_to_id: Dict[int, int] = {}
        for i in range(targets_size):
            name_to_id[self.sequences[i].name + "t"] = i
            id_to_id[i << 1 | 1] = i

        has_name = [True] * targets_size
        has_data = [True] * targets_size
        has_reverse_data = [False] * targets_size

        self.logger.log("[racon_tpu::Polisher::initialize] loaded target "
                        "sequences")
        self.logger.log()

        # reads, with duplicate read-as-target dedup
        # (reference: src/polisher.cpp:228-263)
        sequences_size = 0
        total_sequences_length = 0
        self.sparser.reset()
        with obs_trace.span("racon_tpu.load_sequences", cat="stage",
                            metric="host.parse_s",
                            registry=self.metrics):
            while True:
                chunk_start = len(self.sequences)
                status = self.sparser.parse(self.sequences, CHUNK_SIZE)
                kept: List[Sequence] = []
                n_dropped = 0
                for i in range(chunk_start, len(self.sequences)):
                    seq = self.sequences[i]
                    total_sequences_length += len(seq.data)
                    existing = name_to_id.get(seq.name + "t")
                    if existing is not None:
                        if len(seq.data) != \
                                len(self.sequences[existing].data) or \
                                len(seq.quality) != \
                                len(self.sequences[existing].quality):
                            raise InvalidInputError(
                                f"duplicate sequence {seq.name} with "
                                "unequal data")
                        name_to_id[seq.name + "q"] = existing
                        id_to_id[sequences_size << 1 | 0] = existing
                        n_dropped += 1
                    else:
                        new_id = i - n_dropped
                        name_to_id[seq.name + "q"] = new_id
                        id_to_id[sequences_size << 1 | 0] = new_id
                        kept.append(seq)
                    sequences_size += 1
                del self.sequences[chunk_start:]
                self.sequences.extend(kept)
                if not status:
                    break

        if sequences_size == 0:
            raise InvalidInputError("empty sequences set!")

        n_total = len(self.sequences)
        has_name += [False] * (n_total - targets_size)
        has_data += [False] * (n_total - targets_size)
        has_reverse_data += [False] * (n_total - targets_size)

        window_type = (WindowType.NGS
                       if total_sequences_length / sequences_size <= 1000
                       else WindowType.TGS)
        # recorded for subclasses that predict device-kernel variants
        # or create windows before the align stage finishes
        # (racon_tpu/tpu/polisher.py prewarm + streaming pipeline)
        self.window_type = window_type
        self._targets_size = targets_size

        self.logger.log("[racon_tpu::Polisher::initialize] loaded sequences")
        self.logger.log()

        # parsed overlaps bill the parse budget; internally mapped
        # ones bill the map stage (host.map_s + stage_walls["map"]),
        # which is how the stage reaches calhealth drift and the
        # serve `explain` cost waterfall
        mapping = self.oparser is None
        with obs_trace.span("racon_tpu.load_overlaps", cat="stage",
                            metric=("host.map_s" if mapping
                                    else "host.parse_s"),
                            registry=self.metrics):
            overlaps = self._load_overlaps(name_to_id, id_to_id,
                                           has_data, has_reverse_data)
        if mapping:
            self.stage_walls["map"] = float(
                self.metrics.value("host.map_s", 0.0))
        # a multi-host rank may legitimately own zero overlaps (its
        # targets drew none); only single-process runs treat an empty
        # set as invalid input
        if not overlaps and self._owned_targets is None:
            raise InvalidInputError("empty overlap set!")

        self.logger.log("[racon_tpu::Polisher::initialize] loaded overlaps")
        self.logger.log()

        # materialise reverse complements in the pool
        # (reference: src/polisher.cpp:368-377)
        with obs_trace.span("racon_tpu.transmute", cat="stage"):
            list(self._pool.map(
                lambda args: args[0].transmute(*args[1:]),
                [(s, has_name[j], has_data[j], has_reverse_data[j])
                 for j, s in enumerate(self.sequences)]))

        with obs_trace.span("racon_tpu.align_stage", cat="stage",
                            metric="stage_wall_s.align",
                            registry=self.metrics):
            self.find_overlap_breaking_points(overlaps)

        self.logger.log()
        with obs_trace.span("racon_tpu.build_windows", cat="stage"):
            self._build_windows(targets_size, window_type, overlaps)
        self.logger.log("[racon_tpu::Polisher::initialize] transformed data "
                        "into windows")

    def _poll_cancel(self) -> None:
        """Raise :class:`JobCanceledError` if the serve tier flagged
        this job canceled (r21 rebalancing).  Poll sites sit between
        committed units only, so cancellation never tears a unit."""
        if self._cancel_check is not None and self._cancel_check():
            raise JobCanceledError("job canceled by the serve tier")

    def _configure_stage(self):
        """Apply the r21 staged-input plan to the overlap parser
        before the parse: a validated router-shipped hint wins; a
        sharded polisher with no (valid) hint self-builds the index
        from its own line tables; anything that cannot be exact —
        staging off, line parsers, non-PAF input, malformed rows —
        falls back to the unchanged full parse by returning None."""
        from racon_tpu.io import staging

        if self._owned_targets is None or not staging.stage_enabled() \
                or not hasattr(self.oparser, "set_stage"):
            return None
        plan = None
        if self._stage_hint is not None:
            plan = staging.plan_from_hint(
                self._stage_hint, self.oparser.path, self._target_shard)
        if plan is None:
            names = [self.sequences[i].name
                     for i in range(self._targets_size)]
            index = staging.get_index(self.oparser.path, names)
            if index is None:
                return None
            plan = index.ranges_for(self._owned_targets)
            plan["total_bytes"] = index.total_bytes
        self.oparser.set_stage(plan["ranges"])
        self.metrics.set("host.staged_bytes",
                         int(plan.get("staged_bytes", 0)))
        self.metrics.set("host.parse_skipped_bytes",
                         max(0, int(plan.get("total_bytes", 0))
                             - int(plan.get("staged_bytes", 0))))
        return plan

    def _discover_overlaps(self) -> List[Overlap]:
        """r24 internal mapping: run the minimap-lite mapper over the
        already-loaded reads/targets and return PAF-shaped Overlap
        records, ready for the same transmute/filter loop a parsed
        file takes.  Reads deduplicated into targets are not mapped —
        their only admissible overlap (self vs self) is exactly what
        the ``q_id == t_id`` filter drops anyway."""
        from racon_tpu.obs import decision as obs_decision
        from racon_tpu.overlap import chain as overlap_chain

        params = overlap_chain.params_from_env()
        targets = self.sequences[:self._targets_size]
        queries = self.sequences[self._targets_size:]
        raw, stats = overlap_chain.map_sequences(queries, targets,
                                                 params=params)
        self._map_stats = stats
        self.metrics.add("map_queries", len(queries))
        self.metrics.add("map_overlaps", len(raw))
        self.metrics.add("map_chains_admitted",
                         stats["chains_admitted"])
        self.metrics.add("map_chains_rejected",
                         stats["chains_rejected"])
        obs_decision.DECISIONS.record(
            "map_chain", queries=len(queries),
            targets=len(targets), overlaps=len(raw),
            admitted=stats["chains_admitted"],
            rejected=stats["chains_rejected"],
            masked_entries=stats["masked_entries"],
            knobs=params.doc())
        self.logger.log(
            f"[racon_tpu::Polisher::initialize] mapped {len(queries)} "
            f"reads -> {len(raw)} overlaps "
            f"({stats['chains_rejected']} chains rejected)")
        return raw

    def _load_overlaps(self, name_to_id, id_to_id, has_data,
                       has_reverse_data) -> List[Overlap]:
        """Stream overlaps, transmute, and filter (polisher.cpp:283-354)."""
        if self.oparser is None:
            # internal mapping: same downstream loop, fed from an
            # in-memory single-chunk source instead of a file parser
            self.oparser = _MappedOverlapSource(self._discover_overlaps())
        self._configure_stage()
        overlaps: List[Optional[Overlap]] = []

        def remove_invalid(begin: int, end: int) -> None:
            for i in range(begin, end):
                if overlaps[i] is None:
                    continue
                o = overlaps[i]
                if o.error > self.error_threshold or o.q_id == o.t_id:
                    overlaps[i] = None
                    continue
                if self.type == PolisherType.kC:
                    # keep only the longest overlap per query
                    for j in range(i + 1, end):
                        if overlaps[j] is None:
                            continue
                        if o.length > overlaps[j].length:
                            overlaps[j] = None
                        else:
                            overlaps[i] = None
                            break

        self.oparser.reset()
        l = 0
        while True:
            status = self.oparser.parse(overlaps, CHUNK_SIZE)
            c = l
            for i in range(l, len(overlaps)):
                overlaps[i].transmute(self.sequences, name_to_id, id_to_id)
                if not overlaps[i].is_valid:
                    overlaps[i] = None
                    continue
                while overlaps[c] is None:
                    c += 1
                if overlaps[c].q_id != overlaps[i].q_id:
                    remove_invalid(c, i)
                    c = i
            if not status:
                remove_invalid(c, len(overlaps))
                c = len(overlaps)

            for i in range(l, c):
                if overlaps[i] is None:
                    continue
                if self._owned_targets is not None and \
                        not self._owned_targets[overlaps[i].t_id]:
                    # multi-host: another rank owns this target.  The
                    # drop must come AFTER remove_invalid (the longest
                    # -per-query winner is chosen over ALL targets,
                    # matching single-process output) but BEFORE the
                    # flag marking, so this rank never materializes
                    # reverse complements for reads whose overlaps
                    # all belong to other ranks
                    overlaps[i] = None
                    continue
                if overlaps[i].strand:
                    has_reverse_data[overlaps[i].q_id] = True
                else:
                    has_data[overlaps[i].q_id] = True

            # compact nulls from l onward (reference shrinkToFit,
            # src/polisher.cpp:348-349)
            n_removed_before_c = sum(
                1 for o in overlaps[l:c] if o is None)
            overlaps[l:] = [o for o in overlaps[l:] if o is not None]
            l = c - n_removed_before_c
            if not status:
                break
        return overlaps  # type: ignore[return-value]

    # ------------------------------------------------------------------
    # accelerator seam #1 (reference: src/polisher.cpp:461-483)
    # ------------------------------------------------------------------

    def _batch_decode_breaking_points(self,
                                      overlaps: List[Overlap]) -> None:
        """Vectorized pre-pass: decode breaking points for every
        overlap already carrying ``cigar_runs`` (SAM ingest, or a
        staged device-align pass) in slab-sized batches fanned over
        the pool — ``work(o)`` then sees points set and skips the
        per-overlap walk.  A failed slab is left undecoded so the
        per-overlap path isolates a poison record to its own error."""
        slabs = overlap_mod.iter_decode_slabs(overlaps)
        if not slabs:
            return

        def one(slab):
            try:
                with self.metrics.timer("host.bp_decode_s"):
                    overlap_mod.decode_breaking_points_batch(
                        slab, self.window_length)
            except Exception:
                pass

        if len(slabs) > 1 and self.num_threads > 1:
            list(self._pool.map(one, slabs))
        else:
            for slab in slabs:
                one(slab)

    def find_overlap_breaking_points(self, overlaps: List[Overlap]) -> None:
        self._batch_decode_breaking_points(overlaps)

        def work(o: Overlap) -> None:
            o.find_breaking_points(self.sequences, self.window_length,
                                   aligner=cpu.align)
            self._notify_overlap_done(o)

        self._run_pooled([(work, (o,)) for o in overlaps],
                         "[racon_tpu::Polisher::initialize] aligning "
                         "overlaps",
                         "[racon_tpu::Polisher::initialize] aligned "
                         "overlaps")

    def _run_pooled(self, tasks, bar_message: str,
                    done_message: str) -> list:
        """Fan tasks over the pool with the reference's 20-bin bar."""
        futures = [self._pool.submit(fn, *args) for fn, args in tasks]
        results = []
        step = len(futures) // 20
        for i, f in enumerate(futures):
            self._poll_cancel()
            results.append(f.result())
            if step != 0 and (i + 1) % step == 0 and (i + 1) // step < 20:
                self.logger.bar(bar_message)
        if step != 0:
            self.logger.bar(bar_message)
        else:
            self.logger.log(done_message)
        return results

    def _notify_overlap_done(self, o: Overlap) -> None:
        """Per-overlap completion hook: fired (possibly from a pool
        thread) once ``o.breaking_points`` exists.  The base pipeline
        does nothing; the TPU polisher's streaming pipeline overrides
        this to advance its per-target/per-window completion ledger
        and route the overlap's window fragments as the align stage
        drains (racon_tpu/tpu/polisher.py)."""

    # ------------------------------------------------------------------
    # windowing (reference: src/polisher.cpp:383-456)
    # ------------------------------------------------------------------

    def _create_windows(self, targets_size: int,
                        window_type: WindowType) -> None:
        """Backbone window skeleton per owned target.  Idempotent: the
        streaming pipeline creates the windows BEFORE the align stage
        (so completed targets can enter POA while later ones are still
        aligning) and the staged path creates them here."""
        if self.windows:
            return
        id_to_first_window_id = [0] * (targets_size + 1)
        for i in range(targets_size):
            if self._owned_targets is not None \
                    and not self._owned_targets[i]:
                # multi-host: another rank emits this target; no
                # windows means polish() skips it entirely
                id_to_first_window_id[i + 1] = id_to_first_window_id[i]
                continue
            data = self.sequences[i].data
            quality = self.sequences[i].quality
            k = 0
            for j in range(0, len(data), self.window_length):
                length = min(j + self.window_length, len(data)) - j
                q = (self.dummy_quality[:length] if not quality
                     else quality[j:j + length])
                self.windows.append(Window(i, k, window_type,
                                           data[j:j + length], q))
                k += 1
            id_to_first_window_id[i + 1] = id_to_first_window_id[i] + k
        self._first_window_id = id_to_first_window_id
        self.targets_coverages = [0] * targets_size

    def _overlap_window_fragments(self, o: Overlap):
        """Yield ``(window_id, data, quality, begin, end)`` for every
        breaking-point pair of ``o`` that passes the length/quality
        filters — the routing rule of the staged ``_build_windows``,
        factored out so the streaming seam can route per overlap as
        alignments complete.  Caller clears ``o.breaking_points``."""
        points = o.breaking_points
        if points is None or len(points) == 0:
            return
        import numpy as np

        w = self.window_length
        sequence = self.sequences[o.q_id]
        # check the stored slot: reverse_quality exists iff transmute
        # materialised it; the property would create it as a side
        # effect (reference getter has none, src/sequence.hpp)
        has_quality = bool(sequence.quality) or \
            bool(sequence._reverse_quality)
        quality_src = (sequence.reverse_quality if o.strand
                       else sequence.quality)
        data_src = (sequence.reverse_complement if o.strand
                    else sequence.data)
        pts = np.asarray(points, dtype=np.int64)
        t_first = pts[0::2, 0]
        q_first = pts[0::2, 1]
        t_last = pts[1::2, 0]
        q_last = pts[1::2, 1]
        keep = (q_last - q_first) >= 0.02 * w
        if has_quality and quality_src:
            idx = np.flatnonzero(keep)
            if idx.size:
                # prefix sums turn each fragment's mean quality into
                # two gathers; int64/int64 true division matches the
                # old Python sum()/len() float exactly (sums < 2^53)
                prefix = np.concatenate(([0], np.cumsum(
                    np.frombuffer(quality_src, np.uint8)
                    .astype(np.int64))))
                total = prefix[q_last[idx]] - prefix[q_first[idx]]
                count = q_last[idx] - q_first[idx]
                keep[idx] = ~((total / count - 33)
                              < self.quality_threshold)
        first_wid = self._first_window_id[o.t_id]
        for j in np.flatnonzero(keep).tolist():
            tf, tl = int(t_first[j]), int(t_last[j])
            qf, ql = int(q_first[j]), int(q_last[j])
            window_start = (tf // w) * w
            yield (first_wid + tf // w, data_src[qf:ql],
                   quality_src[qf:ql] if quality_src else None,
                   tf - window_start, tl - window_start - 1)

    def _build_windows(self, targets_size: int, window_type: WindowType,
                       overlaps: List[Overlap]) -> None:
        self._create_windows(targets_size, window_type)
        with self.metrics.timer("host.fragment_s"):
            for o in overlaps:
                if not self._coverage_counted:
                    self.targets_coverages[o.t_id] += 1
                if o.breaking_points is None or \
                        len(o.breaking_points) == 0:
                    # already routed by the streaming seam (the ROUTED
                    # sentinel) or carried no points at all
                    continue
                for wid, data, quality, begin, end in \
                        self._overlap_window_fragments(o):
                    self.windows[wid].add_layer(data, quality, begin,
                                                end)
                o.breaking_points = None

    # ------------------------------------------------------------------
    # accelerator seam #2 + polish (reference: src/polisher.cpp:485-547)
    # ------------------------------------------------------------------

    def _consensus_cached(self, window, epoch=None):
        """One window's POA consensus through the content-addressed
        result cache (racon_tpu/cache): hit -> adopt the cached
        bytes, miss -> compute and fill.  Returns ``(polished_flag,
        was_hit)``.  Windows below the 3-layer polish threshold
        bypass the cache — the backbone copy is cheaper than a
        lookup.  The "cpu" key space is disjoint from the device
        engine's: the two pipelines resolve cost ties independently,
        so their results must never alias."""
        from racon_tpu import cache as rcache

        if len(window.sequences) < 3 or not rcache.enabled():
            return window.generate_consensus(
                self.engine, self.trim), False
        c = rcache.result_cache()
        if epoch is None:
            epoch = rcache.keying.engine_epoch()
        key = rcache.keying.poa_key(
            "cpu", (self.match, self.mismatch, self.gap), self.trim,
            window, epoch)
        v = c.get(key)
        if v is not rcache.MISS:
            cons, ok = v
            window.consensus = cons
            return bool(ok), True
        ok = window.generate_consensus(self.engine, self.trim)
        c.put(key, (window.consensus, ok))
        return ok, False

    def generate_consensuses(self) -> List[bool]:
        """Generate consensus for every window; returns polished flags."""
        from racon_tpu import cache as rcache

        epoch = rcache.keying.engine_epoch() if rcache.enabled() \
            else None
        return self._run_pooled(
            [(lambda w=w: self._consensus_cached(w, epoch)[0], ())
             for w in self.windows],
            "[racon_tpu::Polisher::polish] generating consensus",
            "[racon_tpu::Polisher::polish] generated consensus")

    def polish(self, drop_unpolished_sequences: bool) -> List[Sequence]:
        with obs_trace.span("racon_tpu.polish", cat="stage"):
            return self._polish(drop_unpolished_sequences)

    def _polish(self, drop_unpolished_sequences: bool) -> List[Sequence]:
        self.logger.log()
        with obs_trace.span("racon_tpu.consensus_stage", cat="stage",
                            metric="stage_wall_s.consensus",
                            registry=self.metrics):
            polished_flags = self.generate_consensuses()

        # stitch each target's window run independently and in
        # parallel over the pool (the window list is read-only here);
        # results collect in group order, so output bytes match the
        # old sequential bytearray accumulation exactly
        groups = []
        start = 0
        for i in range(len(self.windows)):
            if i == len(self.windows) - 1 or self.windows[i + 1].rank == 0:
                groups.append((start, i + 1))
                start = i + 1

        def stitch(bounds) -> Optional[Sequence]:
            lo, hi = bounds
            num_polished_windows = sum(
                1 for i in range(lo, hi) if polished_flags[i])
            window = self.windows[hi - 1]
            polished_ratio = num_polished_windows / (window.rank + 1)
            if drop_unpolished_sequences and not polished_ratio > 0:
                return None
            polished_data = b"".join(self.windows[i].consensus
                                     for i in range(lo, hi))
            tags = "r" if self.type == PolisherType.kF else ""
            tags += f" LN:i:{len(polished_data)}"
            tags += f" RC:i:{self.targets_coverages[window.id]}"
            tags += f" XC:f:{polished_ratio:.6f}"
            return Sequence(self.sequences[window.id].name + tags,
                            polished_data)

        with obs_trace.span("racon_tpu.stitch", cat="stage",
                            metric="host.stitch_s",
                            registry=self.metrics):
            if len(groups) > 1 and self.num_threads > 1:
                stitched = list(self._pool.map(stitch, groups))
            else:
                stitched = [stitch(g) for g in groups]
        dst = [s for s in stitched if s is not None]
        self._finish_host_budget()
        self.windows = []
        self.sequences = []
        return dst

    def _finish_host_budget(self) -> None:
        """Derive the run's host-stage budget gauges: total host data
        -plane seconds (CPU-seconds — concurrent stages can sum past
        the wall) and the share of the run wall they represent."""
        host_s = sum(float(self.metrics.value(k, 0.0))
                     for k in ("host.parse_s", "host.map_s",
                               "host.bp_decode_s", "host.fragment_s",
                               "host.stitch_s"))
        self.metrics.set("host.stage_s", round(host_s, 6))
        # calibration health (r16): host stages have no calibrate
        # rate, so drift is measured against the stage's own learned
        # per-unit rate (racon_tpu/obs/calhealth.observe_units) —
        # unit counts are the natural stage denominators
        units = {"host.parse": len(self.sequences),
                 "host.map": int(self.metrics.value("map_queries", 0)),
                 "host.bp_decode": len(self.sequences),
                 "host.fragment": len(self.windows),
                 "host.stitch": self._targets_size}
        for stage, n in units.items():
            wall = float(self.metrics.value(stage + "_s", 0.0))
            if wall > 0:
                obs_calhealth.observe_units(stage, max(1, n), wall,
                                            registry=self.metrics)
        wall = obs_trace.now() - getattr(self, "_t_run_start",
                                         obs_trace.now())
        if wall > 0:
            self.metrics.set("host.share",
                             round(min(1.0, host_s / wall), 6))

    def total_log(self) -> None:
        self.logger.total("[racon_tpu::Polisher::] total =")

    def close(self) -> None:
        """Release per-run resources (the worker pool).  The one-shot
        CLI never needs this (``os._exit`` reaps everything), but a
        long-lived process running many polishes — bench.py, the
        serve daemon — would otherwise leak one thread pool (and
        three parser file handles) per job
        (racon_tpu/serve/session.py calls this per job)."""
        self._pool.shutdown(wait=True)
        for parser in (self.sparser, self.oparser, self.tparser):
            close = getattr(parser, "close", None)
            if close is not None:
                close()
