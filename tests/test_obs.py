"""Unified trace/metrics subsystem (racon_tpu/obs) — ISSUE 4.

Pins the observability contract:

* the metrics registry semantics (counter/gauge/high-water/histogram,
  parent propagation, the registry-backed polisher attributes);
* the Chrome trace-event schema: well-formed JSON, spans properly
  nested per real thread, align + POA stage spans present on a
  device-path polish;
* determinism safety: a tracing-enabled polish emits byte-identical
  FASTA to a tracing-off polish (clocks feed only the trace, never
  control flow);
* the CLI seam: ``--trace`` / ``--metrics-json`` produce
  schema-valid files and do not change the polished bytes;
* the timing lint: no raw ``time.monotonic()`` / ``perf_counter()``
  outside ``racon_tpu/obs/`` and ``utils/logger.py`` (the grep twin
  lives in ci/cpu/obs_tier1.sh).
"""

import json
import os
import re
import subprocess
import sys
import threading

import pytest

from racon_tpu.obs import metrics as obs_metrics
from racon_tpu.obs import provenance
from racon_tpu.obs import trace as obs_trace

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---------------------------------------------------------------------------
# schema helpers
# ---------------------------------------------------------------------------

_VIRTUAL_LANE_TID0 = obs_trace.Tracer._LANE_TID0


def validate_chrome_trace(doc) -> set:
    """Assert the Chrome trace-event schema; returns the span names.

    Nesting is asserted per REAL thread (context-manager spans strictly
    nest by construction); virtual device lanes legitimately hold
    overlapping dispatch intervals under the double-buffered pipeline.
    """
    assert isinstance(doc, dict)
    events = doc.get("traceEvents")
    assert isinstance(events, list) and events
    names = set()
    for ev in events:
        assert isinstance(ev.get("name"), str) and ev["name"]
        # s/t/f are flow events (r14: executor fused-dispatch
        # attribution arrows); they carry an id instead of a dur
        assert ev.get("ph") in ("X", "i", "M", "s", "t", "f"), ev
        assert isinstance(ev.get("pid"), int)
        assert isinstance(ev.get("tid"), int)
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
            names.add(ev["name"])
        if ev["ph"] in ("s", "t", "f"):
            assert isinstance(ev.get("id"), int)
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        if "args" in ev:
            json.dumps(ev["args"])   # args must be JSON-serializable

    per_tid = {}
    for ev in events:
        if ev["ph"] == "X" and ev["tid"] < _VIRTUAL_LANE_TID0:
            per_tid.setdefault(ev["tid"], []).append(ev)
    eps = 1.0   # one microsecond of float slack
    for evs in per_tid.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []   # open span end times
        for ev in evs:
            end = ev["ts"] + ev["dur"]
            while stack and ev["ts"] >= stack[-1] - eps:
                stack.pop()
            if stack:
                assert end <= stack[-1] + eps, (
                    "span crosses its enclosing span's end "
                    f"(name {ev['name']})")
            stack.append(end)
    return names


def validate_metrics_report(doc) -> None:
    assert doc["schema"] == "racon-tpu-metrics-v1"
    env = doc["environment"]
    # resolved knob provenance: every knob carries value + source
    assert "RACON_TPU_PIPELINE" in env["knobs"]
    for ent in env["knobs"].values():
        assert ent["source"] in ("env", "default")
    assert "jax" in env and "host" in env
    assert env["host"]["cpu_count"] >= 1
    run = doc["run"]
    for section in ("counters", "gauges", "histograms"):
        assert section in run
    assert "process" in doc


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_semantics():
    parent = obs_metrics.Registry()
    reg = obs_metrics.Registry(parent=parent)
    reg.add("c")
    reg.add("c", 2)
    reg.set("g", 5)
    reg.peak("hw", 3)
    reg.peak("hw", 7)
    reg.peak("hw", 2)           # high-water never regresses
    reg.observe("h", 1.0)
    reg.observe("h", 3.0)
    assert reg.value("c") == 3
    assert reg.value("g") == 5
    assert reg.value("hw") == 7
    assert reg.value("missing", -1) == -1
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3
    h = snap["histograms"]["h"]
    assert (h["count"], h["sum"], h["min"], h["max"]) == \
        (2, 4.0, 1.0, 3.0)
    # r12: observations also land in the fixed log-spaced buckets
    # (string-keyed in snapshots), one per observation here
    assert sum(h["buckets"].values()) == 2
    # every write propagated into the parent (process-wide totals)
    assert parent.value("c") == 3 and parent.value("hw") == 7
    json.dumps(snap)             # report-ready
    reg.reset()
    assert reg.value("c") == 0 and parent.value("c") == 3


def test_registry_thread_safety():
    reg = obs_metrics.Registry()

    def work():
        for _ in range(1000):
            reg.add("n")

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.value("n") == 8000


def test_metric_attr_descriptor():
    class Obj:
        x = obs_metrics.MetricAttr("x")

        def __init__(self):
            self.metrics = obs_metrics.Registry()
            self.x = 0

    o = Obj()
    o.x += 2.5
    o.x += 1.5
    assert o.x == 4.0
    # the attribute IS the registry entry: no second copy to drift
    assert o.metrics.value("x") == 4.0


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_disabled_records_nothing(monkeypatch):
    monkeypatch.delenv("RACON_TPU_TRACE", raising=False)
    tracer = obs_trace.Tracer()
    assert not tracer.enabled
    tracer.add_span("x", 0.0, 1.0)
    tracer.add_instant("y")
    with pytest.raises(ValueError):
        tracer.write()           # no path configured


def test_tracer_spans_nested_json(tmp_path, monkeypatch):
    path = str(tmp_path / "trace.json")
    monkeypatch.setenv("RACON_TPU_TRACE", path)
    obs_trace.TRACER.clear()
    with obs_trace.span("outer", cat="t", args={"k": 1}):
        with obs_trace.span("inner", cat="t"):
            pass
        obs_trace.TRACER.add_instant("marker")

    def other_thread():
        with obs_trace.span("thread_outer"):
            with obs_trace.span("thread_inner"):
                pass

    t = threading.Thread(target=other_thread, name="obs-test-thread")
    t.start()
    t.join()
    obs_trace.TRACER.add_span("lane_span", obs_trace.now() - 0.01,
                              obs_trace.now(), lane="device")
    out = obs_trace.write_trace()
    assert out == path
    doc = json.load(open(path))
    names = validate_chrome_trace(doc)
    assert {"outer", "inner", "thread_outer", "thread_inner",
            "lane_span"} <= names
    # thread attribution: the two nests live on different tids, and
    # thread-name metadata names them
    by_name = {ev["name"]: ev for ev in doc["traceEvents"]
               if ev["ph"] == "X"}
    assert by_name["outer"]["tid"] != by_name["thread_outer"]["tid"]
    tnames = {ev["args"]["name"] for ev in doc["traceEvents"]
              if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert "obs-test-thread" in tnames and "device" in tnames
    # the virtual lane sorts after real threads
    assert by_name["lane_span"]["tid"] >= _VIRTUAL_LANE_TID0
    obs_trace.TRACER.clear()


def test_span_metric_accumulates_without_tracing(monkeypatch):
    monkeypatch.delenv("RACON_TPU_TRACE", raising=False)
    reg = obs_metrics.Registry()
    with obs_trace.span("timed", metric="wall_s", registry=reg):
        pass
    assert reg.value("wall_s") >= 0.0
    assert "wall_s" in reg.snapshot()["counters"]


# ---------------------------------------------------------------------------
# provenance
# ---------------------------------------------------------------------------

def test_provenance_knobs(monkeypatch):
    monkeypatch.setenv("RACON_TPU_PIPE_MIN", "7")
    monkeypatch.setenv("RACON_TPU_CUSTOM_THING", "x")
    knobs = provenance.resolved_knobs()
    assert knobs["RACON_TPU_PIPE_MIN"] == {"value": "7",
                                           "source": "env"}
    assert knobs["RACON_TPU_PIPELINE"]["source"] == "default"
    assert knobs["RACON_TPU_PIPELINE"]["value"] == "1"
    # ad-hoc RACON_TPU_* vars are swept in even when uncatalogued
    assert knobs["RACON_TPU_CUSTOM_THING"]["value"] == "x"


def test_metrics_report_roundtrip(tmp_path):
    reg = obs_metrics.Registry()
    reg.add("poa_device_s", 1.25)
    path = str(tmp_path / "metrics.json")
    provenance.write_metrics_json(path, run_registry=reg,
                                  details={"extra": 1}, probe=False)
    doc = json.load(open(path))
    validate_metrics_report(doc)
    assert doc["run"]["counters"]["poa_device_s"] == 1.25
    assert doc["details"]["extra"] == 1


# ---------------------------------------------------------------------------
# e2e: tracing-enabled polish is byte-identical and schema-valid
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def obs_dataset(tmp_path_factory):
    from racon_tpu.tools import simulate

    tmp = str(tmp_path_factory.mktemp("obs_data"))
    return simulate.simulate(tmp, genome_len=15_000, coverage=6,
                             read_len=1_000, seed=52, ont=True)


def _polish(dataset, env):
    from racon_tpu.core.polisher import PolisherType, create_polisher

    reads, paf, draft = dataset
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        pol = create_polisher(
            reads, paf, draft, PolisherType.kC, 500, 10.0, 0.3,
            True, 5, -4, -8, num_threads=8, tpu_poa_batches=1,
            tpu_aligner_batches=1)
        pol.initialize()
        out = b"".join(b">" + s.name.encode() + b"\n" + s.data + b"\n"
                       for s in pol.polish(True))
        return out, pol
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_traced_polish_byte_identical_and_schema(obs_dataset,
                                                 tmp_path,
                                                 monkeypatch):
    monkeypatch.delenv("RACON_TPU_TRACE", raising=False)
    plain, _ = _polish(obs_dataset, {})

    trace_path = str(tmp_path / "polish_trace.json")
    obs_trace.TRACER.clear()
    traced, pol = _polish(obs_dataset,
                          {"RACON_TPU_TRACE": trace_path})
    assert traced == plain, (
        "tracing changed output bytes: clocks must never feed "
        "control flow")

    monkeypatch.setenv("RACON_TPU_TRACE", trace_path)
    doc = json.load(open(obs_trace.write_trace()))
    names = validate_chrome_trace(doc)
    # both pipeline stages are covered, nested under their wrappers
    assert "racon_tpu.device_align" in names
    assert "racon_tpu.device_poa" in names
    assert "racon_tpu.align_stage" in names
    assert "racon_tpu.consensus_stage" in names
    # live spans at the layer boundaries, none recorded after the
    # fact (the POA engine's spans: tests/test_trace_spans.py; here
    # the plain run's results fill the result cache first)
    assert {"racon_tpu.initialize", "racon_tpu.load_sequences",
            "racon_tpu.polish", "racon_tpu.stitch"} <= names
    assert not any(n.startswith(("align.rung.", "align.chunk.",
                                 "poa.spec_megabatch_collect"))
                   for n in names)
    # the stitch span and host.stitch_s are one timing
    stitch_us = sum(ev["dur"] for ev in doc["traceEvents"]
                    if ev.get("ph") == "X"
                    and ev["name"] == "racon_tpu.stitch")
    assert stitch_us * 1e-6 == pytest.approx(
        pol.metrics.value("host.stitch_s"), rel=1e-6, abs=1e-6)
    obs_trace.TRACER.clear()

    # the run registry carries every pipeline health counter and the
    # report round-trips through the schema
    m = pol.metrics
    assert m.value("stage_wall_s.device_poa") > 0
    assert m.value("poa_spec_used") >= 0
    assert m.value("ledger_ready_high_water") >= 0
    report = str(tmp_path / "report.json")
    provenance.write_metrics_json(
        report, run_registry=m,
        details={"poa_split_detail": pol.poa_split_detail},
        probe=False)
    rep = json.load(open(report))
    validate_metrics_report(rep)
    gauges = rep["run"]["gauges"]
    for key in ("poa_spec_used", "poa_spec_wasted",
                "pipeline_overlap_s", "poa_device_s",
                "align_device_s", "stage_wall_s.device_align",
                "stage_wall_s.device_poa"):
        assert key in gauges, f"run report missing {key}"


# ---------------------------------------------------------------------------
# CLI seam (subprocess: --trace/--metrics-json + byte identity)
# ---------------------------------------------------------------------------

def _cli_env(cache_dir):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "RACON_TPU_CACHE_DIR": cache_dir,
        "RACON_TPU_CLI_PREWARM": "0",
        # pinned rates: bytes must not depend on calibration state
        "RACON_TPU_RATE_POA_DEV": "0.30",
        "RACON_TPU_RATE_POA_CPU": "2.0",
        "RACON_TPU_RATE_ALIGN_DEV": "1100",
        "RACON_TPU_RATE_ALIGN_CPU": "4.0",
        "RACON_TPU_RATE_ALIGN_WFA_DEV": "700",
        "RACON_TPU_RATE_ALIGN_WFA_CPU": "1.0",
    })
    env.pop("RACON_TPU_TRACE", None)
    env.pop("RACON_TPU_METRICS_JSON", None)
    return env


def test_cli_trace_and_metrics_json(obs_dataset, tmp_path):
    reads, paf, draft = obs_dataset
    trace_path = str(tmp_path / "cli_trace.json")
    report_path = str(tmp_path / "cli_metrics.json")
    base = [sys.executable, "-m", "racon_tpu.cli", "-t", "4",
            "-c", "1", "--tpualigner-batches", "1"]
    inputs = [reads, paf, draft]

    plain = subprocess.run(
        base + inputs, cwd=REPO_ROOT, capture_output=True,
        env=_cli_env(str(tmp_path / "cache_a")), timeout=600)
    assert plain.returncode == 0, plain.stderr.decode()

    traced = subprocess.run(
        base + ["--trace", trace_path,
                "--metrics-json", report_path] + inputs,
        cwd=REPO_ROOT, capture_output=True,
        env=_cli_env(str(tmp_path / "cache_b")), timeout=600)
    assert traced.returncode == 0, traced.stderr.decode()

    assert plain.stdout == traced.stdout, (
        "--trace/--metrics-json changed the polished bytes")
    # one-line pipeline health summary at default verbosity
    assert b"pipeline summary:" in traced.stderr

    names = validate_chrome_trace(json.load(open(trace_path)))
    assert "racon_tpu.run" in names
    assert "racon_tpu.device_align" in names
    assert "racon_tpu.device_poa" in names

    rep = json.load(open(report_path))
    validate_metrics_report(rep)
    assert rep["environment"]["jax"]["backend"] == "cpu"
    assert "capability_probe" in rep["environment"]["host"]
    assert "poa_spec_used" in rep["run"]["gauges"]
    assert "stage_walls" in rep["details"]


# ---------------------------------------------------------------------------
# timing lint: obs owns the clock
# ---------------------------------------------------------------------------

def test_no_raw_timing_outside_obs():
    """New raw time.monotonic()/perf_counter()/time.time() timing
    belongs in racon_tpu/obs (use obs.now()/span()); utils/logger.py
    keeps its own clock to preserve the reference's exact stderr
    format, and tools/wrapper.py stamps scratch filenames with
    wall-clock time (an identifier, not a measurement).  The grep
    twins of this lint run in ci/cpu/obs_tier1.sh and
    ci/cpu/forensics_tier1.sh."""
    pat = re.compile(
        r"time\.monotonic\(|time\.perf_counter\(|time\.time\(")
    allowed = {os.path.join("racon_tpu", "utils", "logger.py"),
               os.path.join("racon_tpu", "tools", "wrapper.py")}
    offenders = []
    pkg = os.path.join(REPO_ROOT, "racon_tpu")
    for dirpath, _, files in os.walk(pkg):
        if os.path.basename(dirpath) == "obs":
            continue
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, REPO_ROOT)
            if rel in allowed:
                continue
            with open(path) as f:
                for ln, line in enumerate(f, 1):
                    if pat.search(line):
                        offenders.append(f"{rel}:{ln}")
    assert not offenders, (
        "raw timing outside racon_tpu/obs (route through "
        "racon_tpu.obs.now/span): " + ", ".join(offenders))
