"""Command-line interface (reference: src/main.cpp).

Same contract as racon: three positional inputs (sequences, overlaps,
target sequences), polished FASTA on stdout, and the same option set with
the CUDA flags mirrored as TPU flags:

  racon:  -c/--cudapoa-batches, -b/--cuda-banded-alignment,
          --cudaaligner-batches     (src/main.cpp:35-38)
  here:   -c/--tpupoa-batches,  -b/--tpu-banded-alignment,
          --tpualigner-batches

``-c`` keeps racon's optional-argument behaviour (bare -c means 1,
src/main.cpp:111-123).  ``-q -1`` disables the quality filter (any
negative threshold always passes).
"""

from __future__ import annotations

import os
import sys

from racon_tpu import __version__
from racon_tpu.core.overlap import InvalidInputError
from racon_tpu.core.polisher import PolisherType, create_polisher
from racon_tpu.io.parsers import (MalformedInputError,
                                  UnsupportedFormatError)

USAGE = """usage: racon-tpu [options ...] <sequences> <overlaps> <target sequences>
       racon-tpu [run] [options ...] [--rounds N] <sequences> <target sequences>
       racon-tpu serve --socket PATH [options ...]
       racon-tpu route --socket PATH --backends S1,S2,.. [--tcp HOST:PORT]
       racon-tpu submit --socket PATH [options ...] <sequences> <overlaps> <target sequences>
       racon-tpu submit --socket PATH [options ...] [--rounds N] <sequences> <target sequences>
       racon-tpu status --socket PATH [--json]
       racon-tpu top (--socket PATH | --fleet S1,S2,..) [--interval S] [--once] [--json]
       racon-tpu metrics (--socket PATH | --fleet S1,S2,..) [--json|--prometheus]
       racon-tpu inspect (--socket PATH | --dump FILE | --fleet ADDR --job-key K) [--job N] [--trace-out FILE] [--json]
       racon-tpu explain (--socket PATH | --metrics-json FILE) [--job N] [--json]

    subcommands (racon_tpu/serve — persistent polishing service):
        serve    start the warm-kernel job daemon on a unix socket
        route    start a fault-tolerant router fronting several
                 serve daemons: health-probed placement, spillover
                 on backpressure, per-backend circuit breakers, and
                 exactly-once crash failover (idempotent job keys +
                 journal dedup); --tcp adds a host-crossing TCP
                 listener with the same framed protocol
        submit   run one polish through a daemon (same options and
                 stdout contract as the one-shot form; --trace FILE
                 saves the job's server-side trace slice;
                 --trace-context ID propagates a caller trace id
                 into the daemon's spans and flight events;
                 --job-key KEY makes the submit idempotent — a
                 duplicate key joins the live job or is answered
                 from the daemon's write-ahead journal record;
                 --retry N retries retryable failures — queue_full,
                 draining, daemon restarting — with jittered
                 exponential backoff)
        status   print a daemon's queue/registry/provenance snapshot
                 (--json for the raw document)
        top      live telemetry view over the daemon's watch stream;
                 --fleet polls many daemons and renders per-daemon
                 rows + the exactly-merged fleet SLO table
                 (--once --json for one machine-readable frame)
        metrics  one-shot telemetry scrape of one daemon or a fleet,
                 as JSON or Prometheus text (fleet samples carry
                 instance="<daemon_id>" labels)
        inspect  render a job's timeline (queue wait, exec, fused
                 dispatches with occupancy) from a live daemon's
                 flight recorder or a post-mortem flight dump;
                 --fleet --job-key K assembles one job's fleet-wide
                 lineage (scatter/rebalance/failover/dedup/gather)
                 with clock-aligned per-daemon lanes and an optional
                 merged Perfetto trace (--trace-out)
        explain  render the decision plane: a job's cost waterfall
                 (stage walls, decision counts) and the per-stage
                 predicted-vs-actual calibration-health table, from
                 a live daemon or a --metrics-json run report


    #default output is stdout
    <sequences>
        input file in FASTA/FASTQ format (can be compressed with gzip)
        containing sequences used for correction
    <overlaps>
        input file in MHAP/PAF/SAM format (can be compressed with gzip)
        containing overlaps between sequences and target sequences;
        OMIT this input (two positionals) to discover overlaps with
        the built-in minimap-lite mapper (racon_tpu/overlap) — no
        minimap2 required
    <target sequences>
        input file in FASTA/FASTQ format (can be compressed with gzip)
        containing sequences which will be corrected

    options:
        -u, --include-unpolished
            output unpolished target sequences
        -f, --fragment-correction
            perform fragment correction instead of contig polishing
            (overlaps file should contain dual/self overlaps!)
        -w, --window-length <int>
            default: 500
            size of window on which POA is performed
        -q, --quality-threshold <float>
            default: 10.0
            threshold for average base quality of windows used in POA
        -e, --error-threshold <float>
            default: 0.3
            maximum allowed error rate used for filtering overlaps
        --no-trimming
            disables consensus trimming at window ends
        -m, --match <int>
            default: 3
            score for matching bases
        -x, --mismatch <int>
            default: -5
            score for mismatching bases
        -g, --gap <int>
            default: -4
            gap penalty (must be negative)
        -t, --threads <int>
            default: 1
            number of threads
        --version
            prints the version number
        -h, --help
            prints the usage
        -c, --tpupoa-batches <int>
            default: 0
            number of batches for TPU accelerated polishing
        -b, --tpu-banded-alignment
            use banding approximation for alignment on TPU
        --tpualigner-batches <int>
            default: 0
            number of batches for TPU accelerated alignment
        --trace <file>
            write a Chrome trace-event JSON of the run (loadable in
            Perfetto / chrome://tracing); RACON_TPU_TRACE equivalent
        --metrics-json <file>
            write the run report (metrics registry + environment
            provenance); RACON_TPU_METRICS_JSON equivalent
        --rounds <int>
            default: 1
            number of polishing rounds: after each round the reads
            are re-mapped against the polished draft and it is
            polished again (rounds past the first always use the
            internal mapper — any supplied overlaps file describes
            the ORIGINAL draft only)
"""


def parse_args(argv):
    """getopt-style parse preserving racon's -c optional-arg quirk."""
    opts = {
        "window_length": 500, "quality_threshold": 10.0,
        "error_threshold": 0.3, "trim": True, "match": 3, "mismatch": -5,
        "gap": -4, "threads": 1, "type": PolisherType.kC,
        "drop_unpolished": True, "tpu_poa_batches": 0,
        "tpu_banded_alignment": False, "tpu_aligner_batches": 0,
        "rounds": 1,
        # observability (racon_tpu/obs): env defaults keep library
        # and CLI runs on one switch
        "trace": os.environ.get("RACON_TPU_TRACE") or None,
        "metrics_json": os.environ.get("RACON_TPU_METRICS_JSON")
        or None,
    }
    positionals = []
    i = 0
    n = len(argv)

    def take_value(flag):
        nonlocal i
        i += 1
        if i >= n:
            print(f"[racon_tpu::] error: missing argument for {flag}!",
                  file=sys.stderr)
            raise SystemExit(1)
        return argv[i]

    while i < n:
        a = argv[i]
        if a in ("-u", "--include-unpolished"):
            opts["drop_unpolished"] = False
        elif a in ("-f", "--fragment-correction"):
            opts["type"] = PolisherType.kF
        elif a in ("-w", "--window-length"):
            opts["window_length"] = int(take_value(a))
        elif a.startswith("--window-length="):
            opts["window_length"] = int(a.split("=", 1)[1])
        elif a in ("-q", "--quality-threshold"):
            opts["quality_threshold"] = float(take_value(a))
        elif a.startswith("--quality-threshold="):
            opts["quality_threshold"] = float(a.split("=", 1)[1])
        elif a in ("-e", "--error-threshold"):
            opts["error_threshold"] = float(take_value(a))
        elif a.startswith("--error-threshold="):
            opts["error_threshold"] = float(a.split("=", 1)[1])
        elif a in ("-T", "--no-trimming"):
            opts["trim"] = False
        elif a in ("-m", "--match"):
            opts["match"] = int(take_value(a))
        elif a in ("-x", "--mismatch"):
            opts["mismatch"] = int(take_value(a))
        elif a in ("-g", "--gap"):
            opts["gap"] = int(take_value(a))
        elif a in ("-t", "--threads"):
            opts["threads"] = int(take_value(a))
        elif a in ("-c", "--tpupoa-batches", "--cudapoa-batches"):
            # optional argument: bare -c means 1 (src/main.cpp:111-123)
            opts["tpu_poa_batches"] = 1
            if i + 1 < n and argv[i + 1] and not argv[i + 1].startswith("-"):
                i += 1
                opts["tpu_poa_batches"] = int(argv[i])
        elif a.startswith("--tpupoa-batches="):
            opts["tpu_poa_batches"] = int(a.split("=", 1)[1])
        elif a in ("-b", "--tpu-banded-alignment", "--cuda-banded-alignment"):
            opts["tpu_banded_alignment"] = True
        elif a in ("--tpualigner-batches", "--cudaaligner-batches"):
            opts["tpu_aligner_batches"] = int(take_value(a))
        elif a.startswith("--tpualigner-batches="):
            opts["tpu_aligner_batches"] = int(a.split("=", 1)[1])
        elif a == "--rounds":
            opts["rounds"] = int(take_value(a))
        elif a.startswith("--rounds="):
            opts["rounds"] = int(a.split("=", 1)[1])
        elif a == "--trace":
            opts["trace"] = take_value(a)
        elif a.startswith("--trace="):
            opts["trace"] = a.split("=", 1)[1]
        elif a == "--metrics-json":
            opts["metrics_json"] = take_value(a)
        elif a.startswith("--metrics-json="):
            opts["metrics_json"] = a.split("=", 1)[1]
        elif a == "--version":
            print(__version__)
            raise SystemExit(0)
        elif a in ("-h", "--help"):
            print(USAGE, end="")
            raise SystemExit(0)
        elif a.startswith("-") and a != "-":
            print(f"[racon_tpu::] error: unknown option {a}!",
                  file=sys.stderr)
            raise SystemExit(1)
        else:
            positionals.append(a)
        i += 1

    return opts, positionals


def _log_run_summary(polisher, opts) -> None:
    """One-line end-of-run health summary at default verbosity: the
    speculative-pipeline counters (adopted, wasted and skipped
    speculation, the ledger's ready-queue high-water mark) used to be
    visible only inside bench runs; a production polish should say
    whether its speculation paid off without re-running under
    bench.py."""
    m = getattr(polisher, "metrics", None)
    if m is None:
        return
    if opts["tpu_poa_batches"] > 0:
        print("[racon_tpu::] pipeline summary: "
              f"spec used {int(m.value('poa_spec_used'))}"
              f"/wasted {int(m.value('poa_spec_wasted'))}"
              f"/skipped {int(m.value('poa_spec_skipped'))} window(s), "
              "ledger ready peak "
              f"{int(m.value('ledger_ready_high_water'))}, "
              f"overlap {float(m.value('pipeline_overlap_s')):.2f} s, "
              f"device poa {float(m.value('poa_device_s')):.2f} s / "
              f"align {float(m.value('align_device_s')):.2f} s",
              file=sys.stderr)
    # host data-plane budget (r7): where host CPU-seconds went and
    # their share of the run wall, so "is the host the wall" is
    # answerable from a production run's stderr (CPU-only runs too)
    print("[racon_tpu::] host budget: "
          f"parse {float(m.value('host.parse_s')):.2f} s, "
          f"map {float(m.value('host.map_s')):.2f} s, "
          f"bp decode {float(m.value('host.bp_decode_s')):.2f} s, "
          f"fragment {float(m.value('host.fragment_s')):.2f} s, "
          f"stitch {float(m.value('host.stitch_s')):.2f} s, "
          f"host share {float(m.value('host.share')):.3f}",
          file=sys.stderr)


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    # serving subcommands dispatch before option parsing: they own
    # their own argv shape (and the serve daemon must NOT inherit
    # the one-shot assumptions below — racing prewarm thread,
    # os._exit — it prewarms once, synchronously, and exits only
    # after a graceful drain)
    if argv and argv[0] == "serve":
        from racon_tpu.serve import server as serve_server
        raise SystemExit(serve_server.main(argv[1:]))
    if argv and argv[0] == "route":
        from racon_tpu.serve import router as serve_router
        raise SystemExit(serve_router.main(argv[1:]))
    if argv and argv[0] == "submit":
        from racon_tpu.serve import client as serve_client
        raise SystemExit(serve_client.main_submit(argv[1:]))
    if argv and argv[0] == "status":
        from racon_tpu.serve import client as serve_client
        raise SystemExit(serve_client.main_status(argv[1:]))
    if argv and argv[0] == "top":
        from racon_tpu.serve import top as serve_top
        raise SystemExit(serve_top.main(argv[1:]))
    if argv and argv[0] == "metrics":
        from racon_tpu.serve import fleet as serve_fleet
        raise SystemExit(serve_fleet.main_metrics(argv[1:]))
    if argv and argv[0] == "inspect":
        from racon_tpu.serve import inspect as serve_inspect
        raise SystemExit(serve_inspect.main(argv[1:]))
    if argv and argv[0] == "explain":
        from racon_tpu.serve import explain as serve_explain
        raise SystemExit(serve_explain.main(argv[1:]))
    if argv and argv[0] == "run":
        # explicit alias for the one-shot form (reads -> assembly
        # without a PAF reads best as `racon-tpu run reads draft`)
        argv = argv[1:]
    try:
        opts, inputs = parse_args(argv)
    except ValueError as exc:
        print(f"[racon_tpu::] error: invalid option value ({exc})!",
              file=sys.stderr)
        raise SystemExit(1)

    if len(inputs) == 2:
        # two positionals = reads + draft: internal overlap discovery
        inputs = [inputs[0], None, inputs[1]]
    elif len(inputs) < 3:
        print("[racon_tpu::] error: missing input file(s)!", file=sys.stderr)
        print(USAGE, end="", file=sys.stderr)
        raise SystemExit(1)

    from racon_tpu import obs
    from racon_tpu.obs import flight as obs_flight
    if opts["trace"]:
        # exported to the environment too, so every module (and the
        # prewarm threads spawned below) sees one switch
        obs.enable_trace(opts["trace"])
    # one-shot flight recording: only persisted when an explicit dump
    # path is configured (a default-on dump would litter TMPDIR on
    # every CLI run); the crash hook still dumps on an unhandled
    # exception so a dying run leaves its record
    flight_dump = os.environ.get("RACON_TPU_FLIGHT_DUMP")
    if flight_dump and obs_flight.enabled():
        obs_flight.FLIGHT.install_dump_on_crash(flight_dump)
    obs_flight.FLIGHT.record(
        "run", inputs=[os.path.basename(p) for p in inputs[:3]
                       if p is not None],
        rounds=opts["rounds"], threads=opts["threads"])

    if opts["tpu_poa_batches"] > 0 or opts["tpu_aligner_batches"] > 0:
        # kick off the AOT-shelf prewarm NOW, before the (multi-second)
        # input parse below: the jax import and the shelved kernel
        # loads run behind the parse instead of after it
        # (racon_tpu/tpu/polisher.py spawn_cli_prewarm)
        try:
            from racon_tpu.tpu.polisher import spawn_cli_prewarm
            spawn_cli_prewarm(opts["match"], opts["mismatch"],
                              opts["gap"], opts["trim"])
        except ImportError:
            pass   # TPU support missing: create_polisher reports it

    try:
        with obs.span("racon_tpu.run", cat="stage"):
            from racon_tpu.overlap import rounds as overlap_rounds
            polished, polisher = overlap_rounds.polish_rounds(
                inputs[0], inputs[1], inputs[2], opts["type"],
                opts["window_length"], opts["quality_threshold"],
                opts["error_threshold"], opts["trim"], opts["match"],
                opts["mismatch"], opts["gap"], opts["threads"],
                rounds=opts["rounds"],
                drop_unpolished=opts["drop_unpolished"],
                tpu_poa_batches=opts["tpu_poa_batches"],
                tpu_banded_alignment=opts["tpu_banded_alignment"],
                tpu_aligner_batches=opts["tpu_aligner_batches"])
        polisher.total_log()
        _log_run_summary(polisher, opts)
    except (InvalidInputError, UnsupportedFormatError,
            MalformedInputError, FileNotFoundError) as exc:
        print(f"[racon_tpu::] error: {exc}", file=sys.stderr)
        raise SystemExit(1)

    out = sys.stdout.buffer
    # one write per record batch instead of 4 syscall-sized pieces per
    # record: serialization is part of the host wall on the mega leg
    out.write(b"".join(b">" + seq.name.encode() + b"\n" + seq.data
                       + b"\n" for seq in polished))
    # flush the TEXT layer before the buffer layer: anything printed
    # via print()/sys.stdout sits in the text wrapper, and os._exit
    # skips the interpreter teardown that would normally drain it --
    # without this a redirected stdout could lose those bytes
    # (ADVICE r5)
    sys.stdout.flush()
    out.flush()
    # run report + trace: written AFTER the polished bytes are safely
    # flushed (the stdout contract comes first) and BEFORE the hard
    # exit below would discard them
    if opts["metrics_json"]:
        from racon_tpu.obs import provenance
        provenance.write_metrics_json(
            opts["metrics_json"], run_registry=polisher.metrics,
            details={
                "rounds": getattr(polisher, "rounds_report", []),
                "stage_walls": {
                    k: round(v, 6) for k, v in
                    getattr(polisher, "stage_walls", {}).items()},
                "poa_split_detail": getattr(polisher,
                                            "poa_split_detail", {}),
                "align_retry_counts": {
                    str(k): v for k, v in
                    getattr(polisher, "align_retry_counts",
                            {}).items()},
                "poa_reject_counts": {
                    str(k): v for k, v in
                    getattr(polisher, "poa_reject_counts",
                            {}).items()},
            })
        print(f"[racon_tpu::] metrics report written to "
              f"{opts['metrics_json']}", file=sys.stderr)
    if obs.TRACER.enabled and obs.TRACER.out_path():
        path = obs.write_trace()
        print(f"[racon_tpu::] trace written to {path} "
              "(open in Perfetto / chrome://tracing)",
              file=sys.stderr)
    # the flight ring must be persisted HERE, before the hard exit
    # below skips interpreter teardown (same bug class as the stdout
    # text-layer flush above): an os._exit would otherwise discard
    # the buffered events with no dump written
    if flight_dump and obs_flight.enabled():
        obs_flight.FLIGHT.record("run_done",
                                 n_sequences=len(polished))
        path = obs_flight.FLIGHT.dump(flight_dump, reason="run_done")
        print(f"[racon_tpu::] flight dump written to {path}",
              file=sys.stderr)
    # hard-exit once the output is flushed: background prewarm
    # compiles may still be in flight, and waiting for them (or
    # letting interpreter teardown abort them mid-C++-call) serves no
    # one -- the binary's contract is the bytes on stdout.  The
    # atexit join of the prewarm threads (tpu/polisher.py
    # join_prewarm_threads) therefore never runs on THIS path; it
    # exists for library/embedded callers that import racon_tpu and
    # let the interpreter exit normally
    sys.stderr.flush()
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
