"""Streaming-pipeline invariants (ISSUE r8).

The cross-stage pipeline (RACON_TPU_PIPELINE, default on) changes WHEN
work runs — windows build and speculative POA megabatches dispatch
while the align ladder is still draining — but never WHO computes a
window or how results stitch: engine assignment stays the
deterministic stage-time rate-model argmin, and speculative results
are only adopted for device-assigned windows.  These tests pin that:

* pipeline on vs off ⇒ byte-identical FASTA (same input, threads,
  devices, pinned rates);
* stage-timing jitter (tiny megabatch caps, small speculative take,
  deeper dispatch queues) cannot move a byte — ordering races in the
  producer/consumer seam would show here as run-to-run diffs;
* the WindowLedger's completion accounting is order-independent and
  drains layer fragments in overlap-ordinal order;
* speculation is aimed: the consumer is fed only the windows the
  stage's split is predicted to hand the device, and that prediction
  cuts with the stage's own split helper.
"""

import os

import pytest

from racon_tpu.core.polisher import PolisherType, create_polisher
from racon_tpu.core.window import WindowLedger


def _fasta(polished):
    return b"".join(b">" + s.name.encode() + b"\n" + s.data + b"\n"
                    for s in polished)


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    from racon_tpu.tools import simulate

    tmp = str(tmp_path_factory.mktemp("pipe_data"))
    return simulate.simulate(tmp, genome_len=20_000, coverage=8,
                             read_len=1_000, seed=33, ont=True)


def _polish_bytes(dataset, env, setup=None):
    """One full device-path polish under ``env`` overrides, returning
    (fasta_bytes, polisher); ``setup`` sees the polisher before it
    initializes."""
    reads, paf, draft = dataset
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        pol = create_polisher(
            reads, paf, draft, PolisherType.kC, 500, 10.0, 0.3,
            True, 5, -4, -8, num_threads=8, tpu_poa_batches=1,
            tpu_aligner_batches=1)
        if setup is not None:
            setup(pol)
        pol.initialize()
        out = _fasta(pol.polish(True))
        return out, pol
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@pytest.fixture(scope="module")
def staged_bytes(dataset):
    """The strictly staged (pipeline-off) reference output."""
    out, _ = _polish_bytes(dataset, {"RACON_TPU_PIPELINE": "0"})
    return out


def test_pipeline_on_off_byte_identical(dataset, staged_bytes):
    out, pol = _polish_bytes(dataset, {"RACON_TPU_PIPELINE": "1"})
    assert out == staged_bytes, (
        "streaming pipeline changed output bytes: speculative "
        "scheduling must never move a window to a different engine "
        "or reorder its layers")
    # the seam ran: ledger fully drained into windows before the
    # stage, and the overlap metric is well-formed
    assert pol.pipeline_overlap_s >= 0.0
    assert pol.poa_spec_used >= 0
    assert pol.poa_split_detail.get("mode") == "rate_model"
    assert pol.poa_split_detail["n_eligible"] == \
        pol.poa_eligible_windows


def test_pipeline_timing_jitter_cannot_move_bytes(dataset,
                                                  staged_bytes):
    """Shake the producer/consumer seam: tiny megabatch caps force
    many small speculative and stage dispatches, a speculative take
    of 2 makes batch composition maximally timing-dependent, and a
    deeper dispatch queue reorders collects vs dispatches.  Any
    ordering race (layer routing, spec adoption, FIFO application)
    diffs against the staged bytes."""
    jitter = {
        "RACON_TPU_PIPELINE": "1",
        "RACON_TPU_POA_MEGABATCH": "4",
        "RACON_TPU_PIPE_MIN": "2",
        "RACON_TPU_PIPE_DEPTH": "3",
    }
    outs = [_polish_bytes(dataset, dict(jitter))[0] for _ in range(2)]
    assert outs[0] == staged_bytes, (
        "jittered pipeline diverged from the staged output")
    assert outs[1] == staged_bytes, (
        "jittered pipeline is not run-to-run deterministic")


def _device_bound(pol, i):
    """Whether the prediction marked window ``i`` device-bound."""
    return (len(pol.windows[i].sequences) - 1, -i) >= pol._spec_floor


def _at_stage(check):
    """A ``setup`` that runs ``check(pol)`` as the POA stage starts,
    when every window holds its final layers (polish frees them)."""
    def setup(pol):
        stage = pol._device_generate_consensuses

        def checked():
            check(pol)
            return stage()
        pol._device_generate_consensuses = checked
    return setup


def test_pipeline_spec_filter_byte_identical(dataset, staged_bytes):
    """Aimed speculation: small megabatches and a speculative take of
    2 make the consumer fire on the CPU backend (the align stage's end
    waits for it to drain the ready queue, so it fires whatever the
    timing); it dispatches only windows the prediction marked
    device-bound, leaves the rest to the stage, and the bytes still
    equal the staged path's."""
    import time

    bound = {}

    def setup(pol):
        done = pol._pipeline_align_done

        def drained():
            t_end = time.monotonic() + 120
            while pol._ledger.n_ready() >= 2 \
                    and time.monotonic() < t_end:
                time.sleep(0.01)
            return done()
        pol._pipeline_align_done = drained
        _at_stage(lambda pol: bound.update(
            (i, _device_bound(pol, i))
            for i in range(len(pol.windows))))(pol)

    out, pol = _polish_bytes(dataset, {
        "RACON_TPU_PIPELINE": "1",
        "RACON_TPU_POA_MEGABATCH": "4",
        "RACON_TPU_PIPE_MIN": "2",
    }, setup)
    assert out == staged_bytes, (
        "aimed speculation changed output bytes")
    spec = pol._spec_results
    assert spec, "the speculative consumer never dispatched"
    assert all(bound[i] for i in spec)
    assert pol.metrics.value("poa_spec_skipped") > 0
    assert pol.poa_spec_used + pol.poa_spec_wasted == len(spec)


def _reference_cut(pol, eligible):
    """The POA stage's device cut written out from its definition,
    independent of the polisher's split helper."""
    from racon_tpu.tpu import polisher as tpu_polisher
    from racon_tpu.utils import calibrate

    seqs = [pol.windows[i].sequences for i in eligible]
    n_workers = 0 if os.environ.get("RACON_TPU_POA_DEVICE_ONLY") \
        else pol.num_threads - 1
    if not n_workers:
        return len(eligible)
    if "RACON_TPU_POA_SPLIT" in os.environ:
        return tpu_polisher._split_cut(
            [len(s) ** 2 for s in seqs],
            float(os.environ["RACON_TPU_POA_SPLIT"]))
    n_dev = len(pol.mesh.devices)
    r_dev, r_cpu, src = calibrate.get_rates(
        "poa", n_dev, pol.POA_DEV_US_PER_UNIT, pol.POA_CPU_US_PER_UNIT,
        pin=pol._calib_pin)
    n_priced = calibrate.host_reserved_workers(n_workers, src)
    units = []
    for s in seqs:
        d = min(len(s) - 1, pol.MAX_DEPTH_PER_WINDOW)
        units.append(d * (1 + d / 48.0) * (len(s[0]) / 500.0))
    return tpu_polisher._rate_split([u * r_dev / n_dev for u in units],
                                    [u * r_cpu / n_priced for u in units])


def _stage_order(pol):
    """The stage's eligible windows in its depth-descending order."""
    elig = [i for i, w in enumerate(pol.windows) if len(w.sequences) >= 3]
    elig.sort(key=lambda i: -len(pol.windows[i].sequences))
    return elig


def _helper_cut(pol, eligible):
    """The split helper's cut over the windows' final depths."""
    depths = [len(pol.windows[i].sequences) - 1 for i in eligible]
    units = [pol._poa_unit(d, len(pol.windows[i].sequences[0]))
             for d, i in zip(depths, eligible)]
    n_workers = pol._tail_workers("RACON_TPU_POA_DEVICE_ONLY")
    return pol._poa_cut(depths, units, n_workers, False)[0]


def _rate_pin(pol, dev, cpu):
    """Rates as a calibration pins them (priced over the reserved-down
    CPU workers), in place of the suite's env pins."""
    from racon_tpu.utils import calibrate

    n_dev = len(pol.mesh.devices)
    pol._calib_pin = {calibrate._machine_key(n_dev): {
        "poa": {"dev": dev, "cpu": cpu}}}


@pytest.mark.parametrize("cell", ["ont_r941_ecoli_ci.contigs",
                                  "ont_r1041_ecoli.contigs"])
def test_spec_prediction_precision(tmp_path, monkeypatch, cell):
    """On the benchmark's contigs (cut to 100 kb), one chip and the
    configuration's frozen rates, the windows predicted device-bound
    at the ledger's seal are the ones the stage's split hands the
    device: at least 90% of them are adopted, and the prediction
    leaves the rest of the windows to the stage."""
    from benchmark.gen import simulate
    from benchmark.run import load_cell
    from racon_tpu.parallel import mesh_utils

    _, _, config, _ = load_cell(cell)
    c = simulate.make_contig(config["data"] | {"contig_len": 100_000},
                             2300000024, 0, 0, str(tmp_path))
    p = config["polish"]
    monkeypatch.delenv("RACON_TPU_RATE_POA_DEV", raising=False)
    monkeypatch.delenv("RACON_TPU_RATE_POA_CPU", raising=False)
    # nothing dispatches: the aim alone is under test
    monkeypatch.setenv("RACON_TPU_PIPE_MIN", str(10 ** 9))
    pol = create_polisher(
        c["reads"], c["paf"], c["draft"], PolisherType.kC,
        p["window_length"], p["quality_threshold"], p["error_threshold"],
        p["trim"], p["match"], p["mismatch"], p["gap"], num_threads=13,
        tpu_poa_batches=1, tpu_aligner_batches=0)
    pol._mesh = mesh_utils.default_mesh(1)
    _rate_pin(pol, *config["rates"]["poa"])
    try:
        pol.initialize()
        eligible = _stage_order(pol)
        cut = _reference_cut(pol, eligible)
        assert _helper_cut(pol, eligible) == cut
        stage = set(eligible[:cut])
        predicted = {i for i in eligible if _device_bound(pol, i)}
        assert 0 < cut < len(eligible) and predicted
        assert len(predicted & stage) >= 0.9 * len(predicted)
        assert len(predicted & stage) >= 0.9 * len(stage)
        assert pol.metrics.value("poa_spec_skipped") \
            == len(eligible) - len(predicted)
    finally:
        pol.close()


@pytest.fixture(scope="module")
def small_dataset(tmp_path_factory):
    from racon_tpu.tools import simulate

    tmp = str(tmp_path_factory.mktemp("pipe_small"))
    return simulate.simulate(tmp, genome_len=6_000, coverage=8,
                             read_len=1_000, seed=34, ont=True)


@pytest.mark.parametrize("mode", ["rate_model", "env_split",
                                  "device_only", "env_pinned"])
def test_split_helper_gives_stage_cut(small_dataset, monkeypatch, mode):
    """The POA stage's cut, in each of its split modes, is the one the
    definition gives, and the shared split helper gives the same cut
    over the same windows: the prediction cannot drift from it."""
    monkeypatch.setenv("RACON_TPU_PIPELINE", "1")
    if mode == "rate_model":
        monkeypatch.delenv("RACON_TPU_RATE_POA_DEV", raising=False)
        monkeypatch.delenv("RACON_TPU_RATE_POA_CPU", raising=False)
    elif mode == "env_split":
        monkeypatch.setenv("RACON_TPU_POA_SPLIT", "0.5")
    elif mode == "device_only":
        monkeypatch.setenv("RACON_TPU_POA_DEVICE_ONLY", "1")
    seen = {}

    def check(pol):
        eligible = _stage_order(pol)
        seen["n"] = len(eligible)
        seen["reference"] = _reference_cut(pol, eligible)
        seen["helper"] = _helper_cut(pol, eligible)

    def setup(pol):
        if mode == "rate_model":
            _rate_pin(pol, 2.0, 2.0)
        _at_stage(check)(pol)

    _, pol = _polish_bytes(small_dataset, {}, setup)
    detail = pol.poa_split_detail
    assert detail["mode"] == mode.replace("env_pinned", "rate_model")
    if mode in ("rate_model", "env_pinned"):
        assert detail["rate_source"] == {"rate_model": "pinned",
                                         "env_pinned": "env"}[mode]
    assert detail["n_eligible"] == seen["n"] > 0
    assert detail["cut"] == seen["reference"] == seen["helper"]


@pytest.mark.parametrize("pipeline", ["1", "0"])
def test_device_collect_error_fails_the_run(dataset, monkeypatch,
                                            pipeline):
    """A POA megabatch whose collect raises fails the polish: its
    windows are never quietly re-done on the CPU lane.  With the
    pipeline on only the speculative consumer's megabatches fail."""
    import threading

    from racon_tpu.tpu import poa as tpu_poa

    orig = tpu_poa.TPUPoaBatchEngine.consensus_batch_async

    def broken(self, windows, trim, pool=None):
        if pipeline == "1" and \
                threading.current_thread().name != "racon-poa-stream":
            return orig(self, windows, trim, pool)

        def collect():
            raise RuntimeError("device megabatch failed")
        return collect

    monkeypatch.setattr(tpu_poa.TPUPoaBatchEngine,
                        "consensus_batch_async", broken)
    with pytest.raises(RuntimeError, match="device megabatch failed"):
        _polish_bytes(dataset, {
            "RACON_TPU_PIPELINE": pipeline,
            "RACON_TPU_PIPE_MIN": "2",
            "RACON_TPU_POA_MEGABATCH": "4",
            "RACON_TPU_CACHE": "0",
        })


def test_tracing_enabled_cannot_move_bytes(dataset, staged_bytes,
                                           tmp_path):
    """Tracing enabled (RACON_TPU_TRACE) + pipeline on must still
    equal the staged, tracing-off bytes: obs clocks feed only the
    trace, never control flow — and the recorded trace must be a
    loadable Chrome trace covering both device stages."""
    import json

    from racon_tpu.obs import trace as obs_trace

    trace_path = str(tmp_path / "pipeline_trace.json")
    obs_trace.TRACER.clear()
    out, _ = _polish_bytes(dataset, {
        "RACON_TPU_PIPELINE": "1",
        "RACON_TPU_TRACE": trace_path,
    })
    assert out == staged_bytes, (
        "tracing-enabled pipeline diverged from the tracing-off "
        "staged output")
    doc = json.load(open(obs_trace.write_trace(trace_path)))
    names = {ev["name"] for ev in doc["traceEvents"]
             if ev.get("ph") == "X"}
    assert "racon_tpu.device_align" in names
    assert "racon_tpu.device_poa" in names
    obs_trace.TRACER.clear()


def test_window_ledger_ready_high_water():
    led = WindowLedger(4)
    led.seal()
    led.push_ready([0, 1, 2])
    led.pop_ready(8, min_n=1)
    led.push_ready([3])
    # high-water tracks the deepest the queue ever got, not its
    # current depth
    assert led.ready_high_water == 3
    assert led.n_ready() == 1


def test_window_ledger_order_independent():
    led = WindowLedger(5)
    # overlap A (ordinal 0) covers windows 0..2; B (ordinal 1)
    # covers 1..3; window 4 is uncovered
    led.register(101, 0, 0, 2)
    led.register(102, 1, 1, 3)
    led.seal()
    assert sorted(led.remaining()) == [101, 102]

    # LATER overlap completes first: windows 1..3 wait for A, but 3
    # (covered only by B) becomes ready with B's fragment
    newly = led.complete(102, [(1, 1, b"GG", None, 0, 1),
                               (1, 3, b"TT", None, 0, 1)])
    assert [wid for wid, _ in newly] == [3]
    assert [fr[2] for fr in dict(newly)[3]] == [b"TT"]

    # duplicate completion is a no-op (the fall-through pass
    # re-notifies everything)
    assert led.complete(102, []) == []

    # A completes: windows 0..2 drain; window 1's stash holds both
    # overlaps' fragments sorted by ORDINAL even though B finished
    # first — the staged _build_windows insertion order
    newly = dict(led.complete(101, [(0, 0, b"AA", None, 0, 1),
                                    (0, 1, b"CC", None, 0, 1)]))
    assert sorted(newly) == [0, 1, 2]
    assert [fr[2] for fr in newly[1]] == [b"CC", b"GG"]
    assert newly[2] == []
    assert led.remaining() == []


def test_window_ledger_ready_queue_min_take():
    led = WindowLedger(3)
    led.seal()
    led.push_ready([0, 1])
    assert led.pop_ready(8, min_n=3) == []     # below the floor
    assert led.pop_ready(1, min_n=2) == [0]    # cap respected
    assert led.pop_ready(8, min_n=1) == [1]
    assert led.n_ready() == 0
