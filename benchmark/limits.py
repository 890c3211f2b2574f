"""Readings that the correctness limits are set from, and the control.

    python3 benchmark/limits.py --workload <cell> --seeds 1,2,... \
        --contigs 3 --controls banded,cpu_lane_draft --control-seeds 1,2,3

One process holds the chip.  After the cell's own warm-up, it polishes
the first ``--contigs`` pool contigs of each seed through the timed
path, as a window does, and prints each contig's residual errors per
100 kbp against its truth.  Then the same for each control on
``--control-seeds``:

* ``banded``: the program's own banded POA (``-b``,
  ``tpu_banded_alignment``), the step that trades accuracy for speed;
* ``cpu_lane_draft``: the CPU POA lane leaves each window it takes as
  the draft's backbone, breaking the configuration's guarantee that
  every window with three or more layers is replaced by its
  consensus.

The benchmark's own runs never run this.  Each reading is one JSON
line on standard output, and in ``--out`` when given.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference, run  # noqa: E402


@contextlib.contextmanager
def cpu_lane_draft():
    """Windows of the CPU POA lane keep their backbone."""
    from racon_tpu.core.polisher import Polisher

    orig = Polisher._consensus_cached

    def backbone(self, window, epoch=None):
        window.consensus = window.sequences[0]
        return False, False

    Polisher._consensus_cached = backbone
    try:
        yield
    finally:
        Polisher._consensus_cached = orig


def control(name: str, config: dict):
    """(config, context manager factory) of a control."""
    if name == "banded":
        return (config | {"polish": config["polish"] | {"banded": True}},
                contextlib.nullcontext)
    if name == "cpu_lane_draft":
        return config, cpu_lane_draft
    raise ValueError(f"unknown control {name!r}")


def readings(config, traffic, seed, n, threads, tmp, ctx) -> dict:
    src = run.ContigSource(config, traffic, seed, tmp, warmups=0,
                           n_pool=n)
    try:
        pool = src.pool()
    finally:
        src.close()
    with ctx:
        results = [run.polish_contig(c, config, threads) for c in pool]
    checks = reference.compare(results, {c["name"]: c for c in pool},
                               None)
    return {"seed": seed, "per_contig": checks["per_contig"],
            "max": checks["checks"]["contig_err_per_100kbp_max"]["value"],
            "walls_s": [r["wall_s"] for r in results],
            "poa_device_windows": [r["registry"].get("poa_device_windows")
                                   for r in results],
            "poa_eligible_windows": [
                r["registry"].get("poa_eligible_windows")
                for r in results]}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--contigs", type=int, default=3)
    p.add_argument("--controls", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--out")
    a = p.parse_args(argv)
    _, cell, config, traffic = run.load_cell(a.workload)
    run.check_environment()
    import jax

    run.require_chips(int(cell["chips"]))
    threads = run.threads_for(config)
    out = open(a.out, "a") if a.out else None

    def emit(**kw):
        s = json.dumps({"cell": a.workload, **kw})
        print(s, flush=True)
        if out:
            out.write(s + "\n")
            out.flush()

    tmp = tempfile.mkdtemp(prefix="racon_limits_")
    try:
        src = run.ContigSource(config, traffic, 0, tmp, n_pool=1)
        try:
            run.warm_up(src, config, threads, len(jax.devices()))
        finally:
            src.close()
        for seed in [int(s) for s in a.seeds.split(",") if s]:
            emit(kind="sound", **readings(
                config, traffic, seed, a.contigs, threads, tmp,
                contextlib.nullcontext()))
        for name in [c for c in a.controls.split(",") if c]:
            cfg, ctx = control(name, config)
            for seed in [int(s) for s in a.control_seeds.split(",") if s]:
                emit(kind=name, **readings(cfg, traffic, seed, a.contigs,
                                           threads, tmp, ctx()))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
