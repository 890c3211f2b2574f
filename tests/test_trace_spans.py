"""Program spans in the JAX profile, and the feed-loop counters.

One span call (``racon_tpu.obs.trace.span``) times a block once and
feeds three outputs: a ``TraceAnnotation`` in a concurrent JAX profile
(on the profiler's clock), the Chrome-JSON buffer, and a counter in
the polisher's registry.  These tests pin that:

* on the default CPU path, the stage spans land on a host plane of
  the profile, and ``racon_tpu.stitch``'s profiled duration is the
  ``host.stitch_s`` counter;
* with the Pallas align rungs and the full-device POA engine stubbed
  (no kernel runs on the CPU), the align rung loop, its CPU lane and
  the POA stage record every feed-loop counter the benchmark reads; a
  slow collect shows up as waiting, not decoding; pool-worker spans
  stay out of the profile but reach the Chrome JSON.
"""

import json
import re
import time

import numpy as np
import pytest

from racon_tpu.core.polisher import PolisherType, create_polisher

SLEEP_S = 0.2


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    from racon_tpu.tools import simulate

    tmp = str(tmp_path_factory.mktemp("span_data"))
    return simulate.simulate(tmp, genome_len=15_000, coverage=6,
                             read_len=1_000, seed=52, ont=True)


def _polish(dataset):
    reads, paf, draft = dataset
    pol = create_polisher(
        reads, paf, draft, PolisherType.kC, 500, 10.0, 0.3, True, 5,
        -4, -8, num_threads=4, tpu_poa_batches=1, tpu_aligner_batches=1)
    try:
        pol.initialize()
        out = pol.polish(True)
    finally:
        pol.close()
    return pol, out


def _profiled(fn, log_dir):
    """``fn()`` under the JAX profiler; returns (its result, the
    host-plane spans as ``benchmark.trace_reduce`` reads them)."""
    import jax
    from jax.profiler import ProfileData

    from benchmark import trace_reduce

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    planes = ProfileData.from_file(trace_reduce.find_xplane(log_dir)).planes
    return out, trace_reduce.host_spans(planes)


def test_stage_spans_on_the_profiler_host_plane(dataset, tmp_path,
                                                monkeypatch):
    monkeypatch.delenv("RACON_TPU_TRACE", raising=False)
    # every pair and window on the CPU lanes: the CPU backend's XLA
    # runtime records each executed thunk in a profile, and a few
    # seconds of scan kernels make a trace of hundreds of MB
    monkeypatch.setenv("RACON_TPU_ALIGN_SPLIT", "0")
    monkeypatch.setenv("RACON_TPU_POA_SPLIT", "0")
    monkeypatch.setenv("RACON_TPU_PIPE_MIN", "1000000")
    (pol, out), spans = _profiled(lambda: _polish(dataset),
                                  str(tmp_path / "prof"))
    assert out
    names = {n for n, _, _ in spans}
    assert {"racon_tpu.initialize", "racon_tpu.load_targets",
            "racon_tpu.load_sequences", "racon_tpu.load_overlaps",
            "racon_tpu.transmute", "racon_tpu.align_stage",
            "racon_tpu.device_align", "racon_tpu.build_windows",
            "racon_tpu.polish", "racon_tpu.consensus_stage",
            "racon_tpu.device_poa", "racon_tpu.stitch"} <= names
    # one span, one timing, two outputs
    prof_s = sum(e - s for n, s, e in spans
                 if n == "racon_tpu.stitch") * 1e-9
    reg_s = pol.metrics.value("host.stitch_s")
    assert abs(prof_s - reg_s) <= 0.05 * reg_s + 1e-3, (prof_s, reg_s)


def _ops_row(query: bytes, target: bytes) -> np.ndarray:
    """The CPU aligner's alignment of one pair as a reversed op row,
    the form the device tapes decode to."""
    from racon_tpu.ops import cpu
    from racon_tpu.tpu import aligner as al

    code = {"M": al.OP_EQ, "=": al.OP_EQ, "X": al.OP_X, "I": al.OP_I,
            "D": al.OP_D}
    ops = []
    for n, c in re.findall(r"(\d+)([MIDX=])", cpu.align(query, target)):
        ops += [code[c]] * int(n)
    return np.array(ops[::-1], np.uint8)


@pytest.fixture
def stub_engines(monkeypatch):
    """Pallas align rungs and the full-device POA kernel, stubbed:
    WFA chunks come back certified (the CPU aligner's rows) after
    ``SLEEP_S``, banded chunks and POA megabatches come back rejected
    after ``SLEEP_S`` (their pairs and windows take the CPU lanes).
    Returns the number of align collects made."""
    from racon_tpu.tpu import align_pallas, executor, poa_pallas

    collects = {"align": 0}

    def align_wfa(self, queries, targets, lq, emax, mesh=None,
                  tenant=None):
        rows = [_ops_row(q, t) for q, t in zip(queries, targets)]

        def collect():
            time.sleep(SLEEP_S)
            collects["align"] += 1
            tapes = np.zeros((len(rows), max(map(len, rows))), np.uint8)
            for k, r in enumerate(rows):
                tapes[k, :len(r)] = r
            return (tapes, np.array([len(r) for r in rows]),
                    np.zeros(len(rows), np.int32))
        return collect

    def align_band(self, queries, targets, lq, lt, wb, mesh=None,
                   centers=None, tenant=None):
        def collect():
            time.sleep(SLEEP_S)
            collects["align"] += 1
            n = len(queries)
            return (np.zeros((n, 16), np.uint8), np.zeros(n, np.int32),
                    np.full(n, align_pallas._BIG, np.int32))
        return collect

    def poa_full_dispatch(seqs, wts, meta, nlay, bblen, **kw):
        b = seqs.shape[0]

        def handle():
            time.sleep(SLEEP_S)
            mout = np.zeros((b, 8), np.int32)
            mout[:, 0] = -1
            mout[:, 2] = poa_pallas.FAIL_VCAP
            return np.zeros((b, 8), np.uint8), mout
        return handle

    monkeypatch.setattr(align_pallas, "available", lambda: True)
    monkeypatch.setattr(align_pallas, "wfa_tape_to_ops",
                        lambda row, n: row[:n])
    monkeypatch.setattr(executor.DeviceExecutor, "align_wfa", align_wfa)
    monkeypatch.setattr(executor.DeviceExecutor, "align_band", align_band)
    monkeypatch.setattr(poa_pallas, "available", lambda: True)
    monkeypatch.setattr(poa_pallas, "poa_full_dispatch",
                        poa_full_dispatch)
    # the WFA rung on (another test module turns it off for the
    # process), the device share of align pairs fixed; no result
    # cache, so every chunk and megabatch reaches the stubs
    monkeypatch.setenv("RACON_TPU_WFA", "1")
    monkeypatch.delenv("RACON_TPU_WFA_EMAX", raising=False)
    monkeypatch.setenv("RACON_TPU_ALIGN_SPLIT", "0.5")
    monkeypatch.setenv("RACON_TPU_CACHE", "0")
    return collects


def _fasta(polished) -> bytes:
    return b"".join(b">" + s.name.encode() + b"\n" + s.data + b"\n"
                    for s in polished)


def test_feed_loop_counters_and_profile(dataset, tmp_path, monkeypatch,
                                        stub_engines):
    from racon_tpu.obs import trace as obs_trace

    monkeypatch.delenv("RACON_TPU_TRACE", raising=False)
    _, plain = _polish(dataset)
    stub_engines["align"] = 0
    trace_path = str(tmp_path / "chrome.json")
    monkeypatch.setenv("RACON_TPU_TRACE", trace_path)
    obs_trace.TRACER.clear()
    (pol, out), spans = _profiled(lambda: _polish(dataset),
                                  str(tmp_path / "prof"))
    # profiled and recorded, or neither: the same bytes
    assert out and _fasta(out) == _fasta(plain)
    m = pol.metrics
    for key in ("align.pack_s", "align.wait_s", "align.decode_s",
                "align.probe_s", "align.device_lane_end_s",
                "align.cpu_lane_end_s", "host.bp_decode_queue_s",
                "device.lead_in_s", "poa_phase_s.export",
                "poa_phase_s.extract"):
        v = m.value(key, None)
        assert v is not None and v >= 0, (key, v)
    # the rung loop ran, and a slow collect is waiting, not decoding
    n = stub_engines["align"]
    assert n >= 1 and any(k.startswith("align_rung_admit.wfa")
                          for k in m.snapshot()["counters"])
    assert m.value("align.wait_s") >= n * SLEEP_S
    assert m.value("align.decode_s") < n * SLEEP_S / 2
    assert m.value("poa_phase_s.dispatch") >= SLEEP_S

    # pool-worker spans stay out of the profile ...
    names = {s[0] for s in spans}
    assert {"racon_tpu.align_rung", "racon_tpu.align_pack",
            "racon_tpu.align_wait", "racon_tpu.align_decode",
            "racon_tpu.align_probe", "racon_tpu.align_lane_wait",
            "racon_tpu.bp_decode_drain", "racon_tpu.align_fallthrough",
            "racon_tpu.poa_pack", "racon_tpu.poa_wait",
            "racon_tpu.poa_extract"} <= names
    assert not names & {"racon_tpu.bp_decode",
                        "racon_tpu.align_cpu_lane",
                        "racon_tpu.poa_cpu_lane"}
    # ... and reach the Chrome JSON
    doc = json.load(open(obs_trace.write_trace(trace_path)))
    chrome = {ev["name"] for ev in doc["traceEvents"]
              if ev.get("ph") == "X"}
    assert {"racon_tpu.bp_decode", "racon_tpu.align_cpu_lane",
            "racon_tpu.align_pack"} <= chrome
    obs_trace.TRACER.clear()
