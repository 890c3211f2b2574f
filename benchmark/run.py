"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name: the cell in
``BENCHMARK.json``, its configuration in ``benchmark/configs/``, its
traffic mix in ``benchmark/traffic/`` and each per-layer metric's
reader in ``benchmark/metrics/``.

A run holds the cell's chips in this one process and never falls back
to the CPU.  Set-up (``setup_s``, from process start): contigs are
generated from the seed in worker processes started before JAX is
imported; JAX and the chip come up; the hybrid split's rates are
written as a frozen calibration (the configuration's ``rates``);
every kernel variant the configuration's traffic reaches
(``warm_variants``) is loaded, or on a checkout's first run compiled;
one warm-up polish of a contig from a separate seed stream runs
through the same entry.  The window polishes the
pool's contigs back to back, one fresh polisher per contig, through
``create_polisher -> initialize() -> polish(True)`` (what
``python -m racon_tpu.cli -c 1 --tpualigner-batches 1`` calls), until
``--seconds`` have passed; the contig in flight then finishes and
counts.  With ``--trace 1`` the window is one contig under the JAX
profiler, and the per-layer metrics are read from it.

After the window, and after the device's memory peak is read, every
contig timed in the window is compared with its simulated truth
(``benchmark/reference.py``).  The last stdout line is the result.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference  # noqa: E402
from benchmark.gen import simulate  # noqa: E402

BENCH_DIR = os.path.join(ROOT, "benchmark")
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")

# knobs that would route work off the device engines (chip_smoke.py)
REFUSED = {
    "RACON_TPU_NO_PALLAS": None,          # any value
    "RACON_TPU_PALLAS_INTERPRET": None,
    "RACON_TPU_PALLAS_ALIGN": "0",
    "RACON_TPU_WFA": "0",
}
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# variant loads at once in set-up: each runs its kernel on an inert
# batch, up to ~2 GiB of device memory for the largest align chunks
WARM_THREADS = 4


class BenchError(RuntimeError):
    pass


def note(**fields) -> None:
    """An earlier line of standard output: what the run did."""
    print(json.dumps(fields), flush=True)


# -- finding things by name ----------------------------------------------

def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """(bench, cell, config, traffic) for the cell called ``name``."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no cell {name!r} in BENCHMARK.json")
    cell = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT, confs[cell["config"]]["file"])
    traffic = load_json(ROOT, "benchmark", "traffic",
                        cell["traffic"] + ".json")
    return bench, cell, config, traffic


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[kind]
            if cell in m.get("workloads", [cell])]


def load_reader(name: str):
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark.metrics." + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- environment and chip ------------------------------------------------

def check_environment() -> None:
    for name, bad in REFUSED.items():
        val = os.environ.get(name)
        if val is not None and (bad is None or val == bad):
            raise BenchError(f"{name}={val!r} forces work off the device "
                             "engines; unset it")
    if importlib.util.find_spec("racon_tpu") is None:
        raise BenchError("racon_tpu is not importable: run from a "
                         "racon-tpu checkout")
    # every cache inside the checkout, at fixed paths: the compile
    # cache and the program's cache root (calibration.json, aot/); the
    # persistent result tier stays off, so no run reads another's
    # results
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE_DIR, "jax")
    os.environ["RACON_TPU_CACHE_DIR"] = os.path.join(CACHE_DIR, "racon_tpu")
    os.environ.pop("RACON_TPU_CACHE_PERSIST", None)
    # the configuration states the split's rates (freeze_rates)
    for name in list(os.environ):
        if name.startswith("RACON_TPU_RATE_") or name in (
                "RACON_TPU_RECALIBRATE", "RACON_TPU_CALIB_FREEZE"):
            del os.environ[name]


def freeze_rates(config: dict, n_dev: int) -> dict:
    """The hybrid split's rates as a user's frozen self-calibration:
    the configuration's ``rates`` stored twice through the program's
    own ``calibrate.store_rates`` (generation 2, frozen), then
    ``RACON_TPU_CALIB_FREEZE`` (the serve daemon's knob) so no run
    stores over them.  The split reads them as ``calibrated`` rates,
    the path a user's frozen calibration takes.  A calibration made
    in each checkout would differ between the parent's and the
    change's (PERF.md).  Returns each stage's rates, source and
    generation as the program reads them."""
    from racon_tpu.utils import calibrate

    with contextlib.suppress(FileNotFoundError):
        os.remove(os.path.join(os.environ["RACON_TPU_CACHE_DIR"],
                               "calibration.json"))
    for stage, (dev, cpu) in config["rates"].items():
        for _ in range(2):
            calibrate.store_rates(stage, n_dev, dev, cpu)
    os.environ["RACON_TPU_CALIB_FREEZE"] = "1"
    stored = next(iter(calibrate.epoch_snapshot()["data"].values()), {})
    out = {}
    for stage in config["rates"]:
        dev, cpu, source = calibrate.get_rates(stage, n_dev, 0.0, 0.0)
        out[stage] = {"dev": dev, "cpu": cpu, "source": source,
                      "gen": stored.get(stage, {}).get("gen")}
    return out


def require_chips(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    from racon_tpu.parallel import mesh_utils
    from racon_tpu.tpu import align_pallas, poa_pallas

    if not (poa_pallas.available() and align_pallas.available()
            and align_pallas.wfa_available()):
        raise BenchError("a Pallas engine is off")
    if mesh_utils.interpret_mode():
        raise BenchError("Pallas kernels would run in interpret mode")
    return devs


def threads_for(config: dict) -> int:
    return max(1, min(int(config["polish"]["threads"]),
                      len(os.sched_getaffinity(0))))


class CompileLog:
    """Monotonic times at which JAX finished a backend compilation."""

    def __init__(self):
        import jax

        self.times = []
        jax.monitoring.register_event_duration_secs_listener(
            self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.times.append(time.monotonic() - duration)

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


# -- contigs -------------------------------------------------------------

class ContigSource:
    """Contigs generated in worker processes (no JAX in them), pool
    contigs first in pool order after the first warm-up contigs."""

    def __init__(self, config: dict, traffic: dict, seed: int, tmp: str,
                 warmups: int = 1, n_pool: int = 0):
        import concurrent.futures
        import multiprocessing

        self.config, self.seed, self.tmp = config, seed, tmp
        self.warmups = warmups
        self.pool_stream = traffic["pool_stream"]
        self.warm_stream = traffic["warmup_stream"]
        n = n_pool or int(config["pool_contigs"])
        workers = max(1, min(n + warmups, len(os.sched_getaffinity(0))))
        self._ex = concurrent.futures.ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn"))
        self._warm = [self._submit(self.warm_stream, i)
                      for i in range(warmups)]
        self._pool = [self._submit(self.pool_stream, i) for i in range(n)]

    def _submit(self, stream: int, i: int):
        return self._ex.submit(
            simulate.make_contig, self.config["data"] | {
                "contig_len": self.config["contig_len"]},
            self.seed, stream, i,
            os.path.join(self.tmp, f"s{stream}c{i}"))

    def warmup(self, i: int) -> dict:
        return self._warm[i].result()

    def pool(self) -> list:
        return [f.result() for f in self._pool]

    def close(self) -> None:
        self._ex.shutdown(wait=True, cancel_futures=True)


def count_lines(path: str) -> int:
    with open(path, "rb") as f:
        return sum(1 for _ in f)


# -- one polish ----------------------------------------------------------

def annotation(traced: bool):
    if not traced:
        return lambda name: contextlib.nullcontext()
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


def polish_contig(contig: dict, config: dict, threads: int,
                  annotate=None) -> dict:
    """One one-shot polish through the user's entry point; returns the
    polished bytes, the polisher's metrics and the host-clock wall."""
    from racon_tpu.core.polisher import PolisherType, create_polisher

    p = config["polish"]
    annotate = annotate or annotation(False)
    t0 = time.monotonic()
    with annotate("bench.create_polisher"):
        pol = create_polisher(
            contig["reads"], contig["paf"], contig["draft"],
            PolisherType.kC, p["window_length"], p["quality_threshold"],
            p["error_threshold"], p["trim"], p["match"], p["mismatch"],
            p["gap"], threads, tpu_poa_batches=p["tpu_poa_batches"],
            tpu_banded_alignment=p["banded"],
            tpu_aligner_batches=p["tpu_aligner_batches"])
    try:
        with annotate("bench.initialize"):
            pol.initialize()
        with annotate("bench.polish"):
            out = pol.polish(True)
        snap = pol.metrics.snapshot()
    finally:
        pol.close()
    wall = time.monotonic() - t0
    reg = {}
    for part in ("counters", "gauges"):
        for k, v in snap[part].items():
            if isinstance(v, (int, float)):
                reg[k] = v
    return {"name": contig["name"], "polished": [s.data for s in out],
            "registry": reg, "wall_s": wall,
            "draft_len": contig["draft_len"]}


# -- the run -------------------------------------------------------------

def warm_variants(config: dict) -> dict:
    """Load every kernel variant the configuration's traffic reaches
    (``warm_variants``, entries of the program's prebuild manifest)
    through the program's own prebuild entry, which runs each on an
    inert batch: a shelf and compile-cache load, or on a checkout's
    first run a compile.  The list is every padded batch of every
    align rung at the length buckets of reads near the 16,384-column
    cap, and every POA megabatch size at the configuration's two depth
    buckets (PERF.md), so that nothing compiles in the window."""
    import concurrent.futures

    from racon_tpu import prebuild
    from racon_tpu.utils.xla_cache import enable_compilation_cache

    enable_compilation_cache()
    t0 = time.monotonic()
    entries = config["warm_variants"]
    failed = []
    with concurrent.futures.ThreadPoolExecutor(WARM_THREADS) as ex:
        futs = [(e, ex.submit(prebuild._build_one, e)) for e in entries]
        for e, fut in futs:
            try:
                fut.result()
            except Exception as exc:  # the window would compile it
                failed.append([e, repr(exc)])
    return {"variants": len(entries), "failed": failed,
            "seconds": time.monotonic() - t0}


def warm_up(src: ContigSource, config: dict, threads: int,
            n_dev: int) -> None:
    """Set-up after JAX is up: frozen rates, the variant list, one
    warm-up polish through the timed entry."""
    rates = freeze_rates(config, n_dev)
    variants = warm_variants(config)
    walls = [polish_contig(src.warmup(i), config, threads)["wall_s"]
             for i in range(src.warmups)]
    note(phase="warmup", rates=rates, warm_variants=variants,
         polishes_s=walls)


def run_window(pool: list, config: dict, threads: int, seconds: float,
               limit: int = 0, annotate=None):
    """Polish pool contigs back to back until ``seconds`` have passed
    (or ``limit`` contigs, when given).  Returns (results, t0, t1)."""
    results = []
    t0 = time.monotonic()
    for contig in pool:
        results.append(polish_contig(contig, config, threads, annotate))
        if limit and len(results) >= limit:
            break
        if not limit and time.monotonic() - t0 >= seconds:
            break
    t1 = time.monotonic()
    if not limit and t1 - t0 < seconds:
        note(phase="window", pool_exhausted=True, contigs=len(results),
             window_s=t1 - t0, seconds=seconds)
    return results, t0, t1


def summed_registry(results: list) -> dict:
    reg = {}
    for r in results:
        for k, v in r["registry"].items():
            reg[k] = reg.get(k, 0) + v
    return reg


def run_cell(cell_name: str, seed: int, seconds: float,
             trace: bool) -> dict:
    """The whole run, returning the result line's object."""
    bench, cell, config, traffic = load_cell(cell_name)
    check_environment()
    tmp = tempfile.mkdtemp(prefix="racon_bench_")
    src = None
    try:
        src = ContigSource(config, traffic, seed, tmp,
                           warmups=int(traffic["warmup_polishes"]))
        devs = require_chips(int(cell["chips"]))[:int(cell["chips"])]
        import jax

        compiles = CompileLog()
        threads = threads_for(config)
        note(phase="setup", cell=cell_name, seed=seed, threads=threads,
             platform=devs[0].platform, chips=len(devs))
        warm_up(src, config, threads, len(jax.devices()))
        pool = src.pool()
        src.close()
        src = None
        for c in pool:
            c["paf_pairs"] = count_lines(c["paf"])
        setup_s = time.monotonic() - _T_START
        if trace:
            result = traced_window(pool, config, traffic, threads,
                                   compiles, tmp)
        else:
            results, t0, t1 = run_window(pool, config, threads, seconds)
            result = {"results": results, "window_s": t1 - t0,
                      "compiles": compiles.between(t0, t1)}
        results = result["results"]
        memory_peak = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devs)
        by_name = {c["name"]: c for c in pool}
        checks = reference.compare(results, by_name,
                                   config["correct"]["limit"])
        if trace:
            out_metrics = per_layer(bench, cell_name, result, by_name)
        else:
            out_metrics = end_to_end(bench, cell_name, result, setup_s)
        device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs),
                  "memory_peak_bytes": memory_peak}
        line = {"correct": checks["correct"],
                "attempted": len(results),
                "failed": checks["failed"],
                "metrics": out_metrics, "device": device}
        if trace:
            tr = result["trace"]
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            line["breakdown"] = {"device_ops": tr["device_ops"],
                                 "idle_gaps": tr["idle_gaps"]}
        note(phase="window", contigs=[r["name"] for r in results],
             walls_s=[r["wall_s"] for r in results],
             window_s=result["window_s"], setup_s=setup_s,
             err_per_100kbp=checks["per_contig"],
             residual_err_per_100kbp=checks["residual_err_per_100kbp"],
             compiles_in_window=result["compiles"])
        # the numbers compared, each beside its limit, as the last key
        line["checks"] = checks["checks"]
        return line
    finally:
        if src is not None:
            src.close()
        shutil.rmtree(tmp, ignore_errors=True)


def traced_window(pool, config, traffic, threads, compiles, tmp) -> dict:
    """``traced_contigs`` pool contigs under the JAX profiler."""
    import jax

    from benchmark import trace_reduce

    log_dir = os.path.join(tmp, "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            results, t0, t1 = run_window(
                pool, config, threads, 0,
                limit=int(traffic["traced_contigs"]),
                annotate=annotation(True))
    finally:
        jax.profiler.stop_trace()
    xplane = trace_reduce.find_xplane(log_dir)
    return {"results": results, "window_s": t1 - t0,
            "compiles": compiles.between(t0, t1),
            "trace": trace_reduce.reduce_file(xplane)}


def end_to_end(bench, cell_name, result, setup_s) -> dict:
    results = result["results"]
    values = {
        "setup_s": setup_s,
        "polish_kbp_per_s": sum(r["draft_len"] for r in results)
        / 1e3 / result["window_s"],
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell_metrics(bench, cell_name, "end_to_end")}


def per_layer(bench, cell_name, result, by_name) -> dict:
    results = result["results"]
    ctx = {"registry": summed_registry(results),
           "draft_mbp": sum(r["draft_len"] for r in results) / 1e6,
           "paf_pairs": sum(by_name[r["name"]]["paf_pairs"]
                            for r in results),
           "trace": result.get("trace"),
           "compiles_in_window": result["compiles"]}
    out = {}
    for m in cell_metrics(bench, cell_name, "per_layer"):
        v = load_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if args.seed < 0:
        p.error("--seed must be a non-negative integer")
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except (BenchError, FileNotFoundError, KeyError) as exc:
        print(f"benchmark: FAILED: {exc!r}", file=sys.stderr)
        return 1
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
