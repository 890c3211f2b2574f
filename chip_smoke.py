"""Chip smoke: one-shot contig polishing on a TPU, end to end.

Drives the user's entry point -- ``create_polisher(...,
tpu_poa_batches=1, tpu_aligner_batches=1)`` -> ``initialize()`` ->
``polish()``, what ``python -m racon_tpu.cli -c 1
--tpualigner-batches 1`` calls -- on the repo's ``mega_ont``
configuration: a seeded ONT-model bacterial assembly (2.3 Mb genome,
30x lognormal 10 kb reads, ``racon_tpu.tools.simulate``), window 500,
``-m 5 -x -4 -g -8``, 8 threads.  The data is generated at run time in
a temporary directory; nothing outside the repo is read.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}``.  Any failure exits non-zero and
prints no result: no TPU, a knob that forces a CPU or interpreter
path, an engine that is off, a stage that put no work on the device,
or a polished edit distance to the truth above 5% of the draft's.

``--chips 4`` runs only the mesh path and what it is compared with:
the same polish on the default mesh (all local chips) and on one
chip, both held to the 5% bound, and sharded POA and WFA dispatches
of one shared megabatch, which must equal the one-chip dispatches
byte for byte.
"""

import argparse
import json
import os
import sys
import tempfile
import time

# knobs that would route work off the device engines
_REFUSED = {
    "RACON_TPU_NO_PALLAS": None,          # any value
    "RACON_TPU_PALLAS_INTERPRET": None,
    "RACON_TPU_PALLAS_ALIGN": "0",
    "RACON_TPU_WFA": "0",
}
_BOUND = 0.05           # polished distance <= 5% of the draft's
_GENOME = dict(genome_len=2_300_000, coverage=30, read_len=10_000,
               seed=13, ont=True)
_WFA_LQ, _WFA_EMAX = 8192, 1024   # the shared WFA megabatch's rung


class SmokeError(RuntimeError):
    pass


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def check_environment() -> None:
    for name, bad in _REFUSED.items():
        val = os.environ.get(name)
        if val is not None and (bad is None or val == bad):
            raise SmokeError(f"{name}={val!r} forces work off the "
                             "device engines; unset it")


def require_tpu():
    import jax

    devs = jax.devices()
    require(devs[0].platform == "tpu",
            f"no TPU: JAX found {devs[0].platform} devices")
    from racon_tpu.parallel import mesh_utils
    from racon_tpu.tpu import align_pallas, poa_pallas

    require(poa_pallas.available(), "Pallas POA engine is off")
    require(align_pallas.available(), "Pallas align engine is off")
    require(align_pallas.wfa_available(), "WFA align engine is off")
    require(not mesh_utils.interpret_mode(),
            "Pallas kernels would run in interpret mode")
    return devs


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, summed over
    every thread (prewarm threads compile in the background, so this
    can overlap the polish wall)."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.s = 0.0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self._EVENTS:
            self.s += duration


def read_fasta_one(path: str) -> bytes:
    with open(path, "rb") as f:
        return b"".join(line.strip() for line in f
                        if not line.startswith(b">"))


def make_workload(tmp: str) -> dict:
    from racon_tpu.tools import simulate

    t0 = time.monotonic()
    reads, paf, draft = simulate.simulate(tmp, **_GENOME)
    return {"reads": reads, "paf": paf, "draft": draft,
            "genome": os.path.join(tmp, "genome.fasta"),
            "simulate_s": time.monotonic() - t0}


def polish(work: dict, mesh=None, keep_windows: int = 0):
    """One polish through the user's entry point.  With ``mesh`` the
    polisher runs on it instead of its default (all local chips).
    With ``keep_windows``, also returns that many of the deepest
    device-eligible windows, as built by initialize()."""
    from racon_tpu.core.polisher import PolisherType, create_polisher

    pol = create_polisher(
        work["reads"], work["paf"], work["draft"], PolisherType.kC,
        500, 10.0, 0.3, True, 5, -4, -8, 8, tpu_poa_batches=1,
        tpu_banded_alignment=False, tpu_aligner_batches=1)
    if mesh is not None:
        pol._mesh = mesh
    t0 = time.monotonic()
    pol.initialize()
    kept = []
    if keep_windows:
        kept = sorted((w for w in pol.windows if len(w.sequences) >= 3),
                      key=lambda w: -len(w.sequences))[:keep_windows]
    t1 = time.monotonic()
    out = pol.polish(True)
    t2 = time.monotonic()
    require(len(out) == 1, f"expected one polished contig, got "
            f"{len(out)}")
    return out[0].data, pol, {"initialize_s": t1 - t0,
                              "polish_s": t2 - t1,
                              "wall_s": t2 - t0}, kept


def stage_counters(pol) -> dict:
    m = pol.metrics
    admitted = sum(v for k, v in m.snapshot()["counters"].items()
                   if k.startswith("align_rung_admit."))
    return {
        "poa_device_windows": int(m.value("poa_device_windows")),
        "poa_eligible_windows": int(m.value("poa_eligible_windows")),
        "poa_reject_counts": {str(k): int(v) for k, v in
                              sorted(pol.poa_reject_counts.items())},
        "poa_device_s": m.value("poa_device_s"),
        "align_device_pairs": int(admitted),
        "align_device_s": m.value("align_device_s"),
        "align_wfa_device_s": m.value("align_wfa_device_s"),
        "align_band_device_s": m.value("align_band_device_s"),
        # where the wall went, on the host clock (the obs registry)
        **{k: m.value(k) for k in (
            "stage_wall_s.align", "stage_wall_s.device_align",
            "stage_wall_s.consensus", "stage_wall_s.device_poa",
            "host.parse_s", "host.bp_decode_s", "host.fragment_s",
            "host.stitch_s", "host.share")},
    }


def check_polish(tag: str, polished: bytes, draft: bytes,
                 genome: bytes, pol, times: dict) -> dict:
    from racon_tpu.ops import cpu

    t0 = time.monotonic()
    d_draft = cpu.edit_distance(draft, genome)
    d_pol = cpu.edit_distance(polished, genome)
    c = stage_counters(pol)
    emit(tag, **times, edit_distance_s=time.monotonic() - t0,
         draft_edit_distance=d_draft, polished_edit_distance=d_pol,
         polished_over_draft=d_pol / max(d_draft, 1), **c)
    require(c["poa_device_windows"] > 0,
            f"{tag}: the POA stage put no window on the device")
    require(c["align_device_pairs"] > 0 and c["align_device_s"] > 0,
            f"{tag}: the align stage put no pair on the device")
    require(d_pol <= _BOUND * d_draft,
            f"{tag}: polished distance {d_pol} is above "
            f"{_BOUND:.0%} of the draft's {d_draft}")
    return c


def peak_bytes(devs) -> list:
    out = []
    for d in devs:
        stats = d.memory_stats()
        require(stats is not None and "peak_bytes_in_use" in stats,
                f"{d} reports no memory stats")
        out.append(int(stats["peak_bytes_in_use"]))
    return out


def run_one_chip(devs) -> None:
    clock = CompileClock()
    with tempfile.TemporaryDirectory(prefix="racon_smoke_") as tmp:
        t0 = time.monotonic()
        work = make_workload(tmp)
        draft = read_fasta_one(work["draft"])
        genome = read_fasta_one(work["genome"])
        emit("setup", simulate_s=work["simulate_s"],
             setup_s=time.monotonic() - t0, genome_len=len(genome),
             draft_len=len(draft))
        polished, pol, times, _ = polish(work)
        times["compile_s"] = clock.s
        check_polish("polish", polished, draft, genome, pol, times)
    emit("memory", peak_bytes_in_use=peak_bytes(devs[:1]))


def shared_poa_equal(windows, mesh_all, caps) -> dict:
    """One megabatch of real windows through the sharded kernel and
    through one chip: the consensus bytes and flags must be equal."""
    from racon_tpu.tpu.poa import TPUPoaBatchEngine

    vcap, lcap = caps
    outs = []
    for mesh in (mesh_all, None):
        eng = TPUPoaBatchEngine(5, -4, -8, vcap=vcap, lcap=lcap,
                                mesh=mesh)
        outs.append(eng.consensus_batch_async(windows, True)())
    require(outs[0] == outs[1], "sharded POA megabatch differs from "
            "the one-chip dispatch")
    return {"windows": len(windows),
            "device_consensuses": sum(c is not None
                                      for c, _ in outs[0])}


def shared_wfa_equal(work: dict, mesh_all) -> dict:
    """One WFA megabatch of real read/draft pairs (their PAF spans)
    sharded and on one chip: tapes, entry counts and distances must
    be equal."""
    import numpy as np

    from racon_tpu.core.sequence import _COMPLEMENT
    from racon_tpu.tpu import align_pallas

    draft = read_fasta_one(work["draft"])
    n = align_pallas.chunk_pairs(
        align_pallas.wfa_per_pair_bytes(_WFA_LQ, _WFA_EMAX),
        len(mesh_all.devices))
    spans = {}
    with open(work["paf"], "rb") as f:
        for line in f:
            c = line.split(b"\t")
            qb, qe, tb, te = int(c[2]), int(c[3]), int(c[7]), int(c[8])
            if max(qe - qb, te - tb) <= _WFA_LQ:
                spans[c[0]] = (qb, qe, c[4] == b"-", tb, te)
            if len(spans) == n:
                break
    queries, targets = [], []
    with open(work["reads"], "rb") as f:
        while len(queries) < len(spans):
            name = f.readline()[1:].strip()
            seq = f.readline().strip()
            f.readline()
            f.readline()
            if not name:
                break
            if name in spans:
                qb, qe, rc, tb, te = spans[name]
                q = seq[qb:qe]
                queries.append(q.translate(_COMPLEMENT)[::-1] if rc
                               else q)
                targets.append(draft[tb:te])
    outs = [align_pallas.wfa_dispatch(queries, targets, _WFA_LQ,
                                      _WFA_EMAX, mesh=mesh)()
            for mesh in (mesh_all, None)]
    for a, b in zip(*outs):
        require(np.array_equal(a, b), "sharded WFA megabatch differs "
                "from the one-chip dispatch")
    dists = outs[0][2]
    return {"pairs": len(queries),
            "finished": int((dists <= _WFA_EMAX).sum())}


def run_mesh(devs) -> None:
    from racon_tpu.parallel import mesh_utils

    require(len(devs) >= 2, f"--chips 4 needs several chips, JAX "
            f"found {len(devs)}")
    clock = CompileClock()
    mesh_all = mesh_utils.default_mesh()
    with tempfile.TemporaryDirectory(prefix="racon_smoke_") as tmp:
        t0 = time.monotonic()
        work = make_workload(tmp)
        draft = read_fasta_one(work["draft"])
        genome = read_fasta_one(work["genome"])
        emit("setup", simulate_s=work["simulate_s"],
             setup_s=time.monotonic() - t0, genome_len=len(genome),
             draft_len=len(draft), chips=len(devs))
        polished, pol, times, kept = polish(work, keep_windows=256)
        require(len(pol.mesh.devices) == len(devs),
                "the default mesh does not span every chip")
        times["compile_s"] = clock.s
        check_polish(f"polish_mesh{len(devs)}", polished, draft,
                     genome, pol, times)
        caps = pol._poa_caps()
        c0 = clock.s
        polished1, pol1, times1, _ = polish(
            work, mesh=mesh_utils.default_mesh(1))
        times1["compile_s"] = clock.s - c0
        check_polish("polish_mesh1", polished1, draft, genome, pol1,
                     times1)
        emit("shared_poa_megabatch",
             **shared_poa_equal(kept, mesh_all, caps))
        emit("shared_wfa_megabatch", **shared_wfa_equal(work, mesh_all))
    emit("memory", peak_bytes_in_use=peak_bytes(devs))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the mesh path and its one-chip "
                        "comparison")
    args = p.parse_args(argv)
    try:
        check_environment()
        try:
            import racon_tpu  # noqa: F401
        except ImportError as exc:
            raise SmokeError(f"run from a racon-tpu checkout: {exc}")
        devs = require_tpu()
        (run_mesh if args.chips == 4 else run_one_chip)(devs)
    except SmokeError as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
