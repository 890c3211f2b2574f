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
  drains layer fragments in overlap-ordinal order.
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


def _polish_bytes(dataset, env):
    """One full device-path polish under ``env`` overrides, returning
    (fasta_bytes, polisher)."""
    reads, paf, draft = dataset
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        pol = create_polisher(
            reads, paf, draft, PolisherType.kC, 500, 10.0, 0.3,
            True, 5, -4, -8, num_threads=8, tpu_poa_batches=1,
            tpu_aligner_batches=1)
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
