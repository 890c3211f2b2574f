"""Per-run environment provenance + the ``--metrics-json`` run report.

A BENCH_*.json trajectory is only reproducible from a run artifact if
the artifact records the environment that produced it: the resolved
``RACON_TPU_*`` knob values (env-set AND defaulted), the jax backend,
and the host-capability probe bench.py scales its wall budgets by.
:func:`write_metrics_json` emits one self-describing JSON document:

    {"schema": "racon-tpu-metrics-v1",
     "environment": {knobs, jax, host},
     "run": <per-run registry snapshot>,
     "process": <global registry snapshot>,
     "details": {...}}                      # free-form (split detail &c)

BASELINE.md's budget-model terms map 1:1 onto the ``run`` section's
metric names (see BASELINE.md "Observability: metric names").
"""

from __future__ import annotations

import json
import os
import sys

#: knob catalog: name -> default as the code resolves it ("" = unset).
#: Swept IN ADDITION to any RACON_TPU_* actually present in the
#: environment, so pinned rates and ad-hoc overrides always appear.
KNOWN_KNOBS = {
    "RACON_TPU_PIPELINE": "1",
    "RACON_TPU_PIPE_DEPTH": "2",
    "RACON_TPU_PIPE_MIN": "32",
    "RACON_TPU_CLI_PREWARM": "1",
    "RACON_TPU_POA_MEGABATCH": "256",
    "RACON_TPU_POA_BATCH": "0",
    "RACON_TPU_POA_SWIN": "",
    "RACON_TPU_POA_KRANK": "",
    "RACON_TPU_ALIGN_BUDGET": str(4 << 30),
    "RACON_TPU_MAX_ALIGN_DIM": "16384",
    "RACON_TPU_WFA": "1",
    "RACON_TPU_WFA_EMAX": "2048",
    "RACON_TPU_WFA_MAX_MB": "256",
    "RACON_TPU_NO_PALLAS": "",
    "RACON_TPU_PALLAS_INTERPRET": "",
    "RACON_TPU_STEAL": "",
    "RACON_TPU_POA_SPLIT": "",
    "RACON_TPU_ALIGN_SPLIT": "",
    "RACON_TPU_POA_DEVICE_ONLY": "",
    "RACON_TPU_ALIGN_DEVICE_ONLY": "",
    "RACON_TPU_RECALIBRATE": "",
    # host data plane (r7): vectorized ingest escape hatch, batched
    # breaking-point decode slab budget, POA-split host reserve
    "RACON_TPU_FAST_IO": "1",
    "RACON_TPU_BP_COLS": "4000000",
    "RACON_TPU_POA_HOST_RESERVE": "0.25",
    "RACON_TPU_CACHE_DIR": "",
    "RACON_TPU_TRACE": "",
    "RACON_TPU_METRICS_JSON": "",
    # serving (racon_tpu/serve): queue bound, worker count, idle
    # self-shutdown, admission wall cap, calibration store freeze
    "RACON_TPU_SERVE_QUEUE": "8",
    "RACON_TPU_SERVE_JOBS": "2",
    "RACON_TPU_SERVE_IDLE_S": "0",
    "RACON_TPU_SERVE_MAX_WALL_S": "",
    "RACON_TPU_SERVE_ALIGN_MBPS": "",
    "RACON_TPU_SERVE_POA_MBPS": "",
    "RACON_TPU_CALIB_FREEZE": "",
    # cross-job fused device executor (r13, racon_tpu/tpu/executor):
    # fusion off-switch, fusion window, per-tenant in-flight quota
    "RACON_TPU_FUSE": "1",
    "RACON_TPU_FUSE_FORCE": "0",
    "RACON_TPU_FUSE_WAIT_MS": "5",
    "RACON_TPU_SERVE_TENANT_QUOTA": "2",
    # serving telemetry (r12): background sampler period for the
    # queue/device-util gauges (0 = off; read side only, never
    # control flow), bench regression gate opt-in
    "RACON_TPU_SERVE_SAMPLE_S": "0",
    "RACON_TPU_BENCH_GATE": "",
    # flight recorder (r14, racon_tpu/obs/flight.py): off-switch,
    # ring capacity in events, dump path (daemon defaults to
    # $TMPDIR/racon-tpu-flight-<pid>.json; the one-shot CLI only
    # dumps when this is set)
    "RACON_TPU_FLIGHT": "1",
    "RACON_TPU_FLIGHT_RING": "4096",
    "RACON_TPU_FLIGHT_DUMP": "",
    # fleet telemetry plane (r15, racon_tpu/serve/fleet.py): scrape
    # period of the background fleet scraper, per-target request
    # timeout, and the age past which a daemon's last-known snapshot
    # is reported stale
    "RACON_TPU_FLEET_INTERVAL_S": "1.0",
    "RACON_TPU_FLEET_TIMEOUT_S": "5.0",
    "RACON_TPU_FLEET_STALE_S": "10.0",
    # decision plane (r16, racon_tpu/obs/decision.py + calhealth.py):
    # per-unit decision-record off-switch and exemplar-ring capacity
    # (telemetry only — `racon-tpu explain` and the drift tables read
    # it, control flow never does)
    "RACON_TPU_DECISIONS": "1",
    "RACON_TPU_DECISIONS_RING": "2048",
    # durability plane (r17, racon_tpu/serve/journal.py): the serve
    # tier's write-ahead job journal ("0" = exactly the pre-r17
    # daemon), where it lives (default: beside the socket), whether
    # every append fsyncs, and the deterministic fault-injection
    # harness (racon_tpu/obs/faultinject.py, "<site>:<nth>" —
    # test-only, SIGKILLs the process at the nth arrival)
    "RACON_TPU_JOURNAL": "1",
    "RACON_TPU_JOURNAL_DIR": "",
    "RACON_TPU_JOURNAL_FSYNC": "1",
    "RACON_TPU_FAULT": "",
    # fleet router (r19, racon_tpu/serve/router.py): health-probe
    # period/timeout, circuit-breaker open threshold + cooldown, and
    # the optional TCP bind.  Placement policy only — which backend
    # runs a job never changes the job's bytes, so cache/keying.py
    # EXCLUDES all of these from the engine epoch.
    "RACON_TPU_ROUTE_PROBE_S": "1.0",
    "RACON_TPU_ROUTE_PROBE_TIMEOUT_S": "2.0",
    "RACON_TPU_ROUTE_BREAKER_FAILS": "3",
    "RACON_TPU_ROUTE_BREAKER_COOLDOWN_S": "5.0",
    "RACON_TPU_ROUTE_TCP": "",
    # result cache (r18, racon_tpu/cache/): content-addressed unit
    # memoization off-switch, in-process LRU budget in MB, and the
    # shared persistent tier ("1" = <cache_root()>/results, any other
    # non-empty value = an explicit directory).  Policy-only knobs:
    # they never change output bytes, so cache/keying.py EXCLUDES
    # them from the engine epoch that keys every cached result.
    "RACON_TPU_CACHE": "1",
    "RACON_TPU_CACHE_MB": "256",
    "RACON_TPU_CACHE_PERSIST": "",
    # scatter/gather mega-job sharding (r20, racon_tpu/serve/
    # scatter.py): auto-scatter threshold on the predicted wall
    # ("" = scatter only on an explicit --shards) and the shard-count
    # cap.  Shard count is placement policy — a shard's bytes are
    # the target_slice contract's, so cache/keying.py EXCLUDES both
    # from the engine epoch.
    "RACON_TPU_SCATTER_MIN_WALL_S": "",
    "RACON_TPU_SCATTER_MAX_SHARDS": "8",
    # r21 shard-aware staging + straggler rebalancing: staged parsing
    # is pinned byte-identical to the full parse (RACON_TPU_STAGE=0
    # is the escape hatch), and the rebalance factor only moves WHERE
    # a shard runs — both epoch-excluded like every placement knob.
    "RACON_TPU_STAGE": "1",
    "RACON_TPU_SCATTER_REBALANCE": "2.5",
    # r22 closed control loop: content-affinity routing (sketch-priced
    # placement), the adaptive fusion window, drift-triggered
    # recalibration epochs, and the deadline-class SLO targets.  All
    # pure policy — placement, pacing and admission, never bytes — so
    # cache/keying.py EXCLUDES every one from the engine epoch.
    "RACON_TPU_ROUTE_AFFINITY": "1",
    "RACON_TPU_FUSE_ADAPT": "0",
    "RACON_TPU_CALIB_DRIFT_EPOCH": "0",
    "RACON_TPU_CLASS_TARGET_P99_S": "2.0",
    "RACON_TPU_CLASS_HEADROOM": "0.125",
    # r24 internal overlap discovery (racon_tpu/overlap): the mapper
    # knobs select which overlaps exist, so they CHANGE BYTES — none
    # of k/w/occ/min-chain/band/max-gap may be EPOCH_EXCLUDEd; they
    # fold into the cache engine epoch like match/mismatch/gap do.
    "RACON_TPU_MAP_K": "13",
    "RACON_TPU_MAP_W": "5",
    "RACON_TPU_MAP_OCC": "64",
    "RACON_TPU_MAP_MIN_CHAIN": "4",
    "RACON_TPU_MAP_BAND": "500",
    "RACON_TPU_MAP_MAX_GAP": "10000",
    # ...whereas these two are placement/pricing only: device seeding
    # is pinned bit-identical to the host build, and the map
    # throughput prior feeds admission estimates — both excluded.
    "RACON_TPU_MAP_DEVICE_SEED": "0",
    "RACON_TPU_SERVE_MAP_MBPS": "8.0",
}

# host-capability probe reference wall (bench.py's budget scaling):
# a fixed native edit-distance probe (100 kb pair, 10% divergence,
# seeded) measured on the r6 reference host
REF_PROBE_S = 0.27

_probe_cache: list = []


def resolved_knobs() -> dict:
    """Every RACON_TPU_* knob with its resolved value and source."""
    out = {}
    names = set(KNOWN_KNOBS)
    names.update(k for k in os.environ if k.startswith("RACON_TPU_"))
    for name in sorted(names):
        env = os.environ.get(name)
        out[name] = {
            "value": env if env is not None
            else KNOWN_KNOBS.get(name, ""),
            "source": "env" if env is not None else "default",
        }
    return out


def jax_info() -> dict:
    """Backend facts, without forcing a jax import on runs that never
    touched the device path."""
    if "jax" not in sys.modules:
        return {"imported": False}
    try:
        import jax
        devs = jax.devices()
        return {"imported": True, "version": jax.__version__,
                "backend": devs[0].platform, "n_devices": len(devs)}
    except Exception as exc:
        return {"imported": True,
                "error": f"{type(exc).__name__}: {exc}"}


def host_probe() -> dict:
    """Measured host capability: best-of-3 wall of a fixed native
    edit-distance probe vs the reference host, and the wall-budget
    factor bench.py derives from it.  Cached per process (the probe
    costs ~0.3-1 s); never raises."""
    if _probe_cache:
        return _probe_cache[0]
    from racon_tpu.obs.trace import now

    out = {"ref_wall_s": REF_PROBE_S}
    try:
        import numpy as np

        from racon_tpu.ops import cpu

        rng = np.random.default_rng(42)
        acgt = np.frombuffer(b"ACGT", np.uint8)
        g = acgt[rng.integers(0, 4, 100_000)]
        m = g.copy()
        idx = rng.random(len(m)) < 0.10
        m[idx] = acgt[rng.integers(0, 4, int(idx.sum()))]
        q, t = g.tobytes(), m.tobytes()
        cpu.get_library()             # build outside the timing
        best = None
        for _ in range(3):
            t0 = now()
            cpu.edit_distance(q, t)
            dt = now() - t0
            best = dt if best is None else min(best, dt)
        out["probe_wall_s"] = round(best, 4)
        # never tighten below the nominal estimates; cap the slack a
        # pathological host can claim
        out["budget_factor"] = round(
            min(max(best / REF_PROBE_S, 1.0), 4.0), 3)
    except Exception as exc:
        out["error"] = f"{type(exc).__name__}: {exc}"
        out["budget_factor"] = 1.0
    _probe_cache.append(out)
    return out


_identity_cache: dict = {}


def daemon_identity(socket_path: str = None) -> dict:
    """Stable identity block for a serve daemon — attached to every
    ``metrics``/``health``/``watch``/``status`` frame so a fleet
    scraper (racon_tpu/serve/fleet.py) can attribute telemetry to a
    process, not a socket path: sockets get reused across restarts,
    ``daemon_id`` never is (it hashes host+socket+pid+start wall
    time).  The static fields are computed once per process per
    socket; ``backend`` is re-read each call because jax only imports
    after prewarm.  Lives in obs/ because the start epoch is a
    wall-clock stamp (an identifier, not a measurement) — the one
    place raw ``time.time`` is sanctioned (see the obs timing
    lint)."""
    import hashlib
    import socket as _socket
    import time

    key = socket_path or ""
    if key not in _identity_cache:
        host = _socket.gethostname()
        start = time.time()
        raw = f"{host}|{key}|{os.getpid()}|{start:.6f}"
        import racon_tpu

        _identity_cache[key] = {
            "daemon_id":
                hashlib.sha1(raw.encode()).hexdigest()[:12],
            "host": host,
            "pid": os.getpid(),
            "socket": key or None,
            "start_epoch": round(start, 3),
            "version": racon_tpu.__version__,
        }
    ident = dict(_identity_cache[key])
    ji = jax_info()
    ident["backend"] = ji.get("backend") if ji.get("imported") \
        else None
    return ident


def environment(probe: bool = True) -> dict:
    env = {
        "knobs": resolved_knobs(),
        "jax": jax_info(),
        "host": {"cpu_count": os.cpu_count(),
                 "platform": sys.platform},
    }
    if probe:
        env["host"]["capability_probe"] = host_probe()
    return env


def metrics_doc(run_registry=None, details=None,
                probe: bool = True) -> dict:
    """The run report as a dict — what ``--metrics-json`` writes and
    what a served job embeds in its response frame
    (racon_tpu/serve/session.py)."""
    from racon_tpu.obs.metrics import REGISTRY

    from racon_tpu.obs.devutil import DEVICE_UTIL

    doc = {
        "schema": "racon-tpu-metrics-v1",
        "environment": environment(probe=probe),
        "run": (run_registry.snapshot()
                if run_registry is not None else None),
        "process": REGISTRY.snapshot(),
        "device_util": DEVICE_UTIL.snapshot(),
    }
    if details:
        doc["details"] = details
    return doc


def write_metrics_json(path: str, run_registry=None, details=None,
                       probe: bool = True) -> str:
    """Write the run report (atomic replace).  Returns ``path``."""
    doc = metrics_doc(run_registry=run_registry, details=details,
                      probe=probe)
    tmp = path + f".tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, path)
    return path
