"""The harness finds everything by name, keeps to the benchmark's
contract, and refuses what it must refuse."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import run

ROOT = run.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [c["name"] for c in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name(cell):
    bench, c, config, traffic = run.load_cell(cell)
    assert config["name"] == c["config"]
    assert traffic["name"] == c["traffic"]
    assert c["chips"] in (1, 4) and len(c["why"]) <= 200
    assert run.cell_metrics(bench, cell, "end_to_end")
    assert run.cell_metrics(bench, cell, "per_layer")
    assert "setup_s" in {m["name"] for m in
                         run.cell_metrics(bench, cell, "end_to_end")}


@pytest.mark.parametrize("conf", BENCH["configs"],
                         ids=lambda c: c["name"])
def test_config_file(conf):
    cfg = json.load(open(os.path.join(ROOT, conf["file"])))
    assert cfg["name"] == conf["name"]
    assert cfg["source"] == conf["source"]
    assert cfg["reduced"] == conf["reduced"]
    assert conf["name"] in {c["config"] for c in BENCH["workloads"]}
    assert cfg["correct"]["limit"] is not None


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_reader_loads_by_name(metric):
    read = run.load_reader(metric["name"])
    assert callable(read)
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert set(metric["workloads"]) <= set(CELLS)


def test_names_units_and_bounds():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += CELLS + [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_refuses_a_cpu_device():
    with pytest.raises(run.BenchError, match="no TPU"):
        run.require_chips(1)


@pytest.mark.parametrize("knob,value", [("RACON_TPU_NO_PALLAS", "1"),
                                        ("RACON_TPU_WFA", "0")])
def test_refuses_knobs(monkeypatch, knob, value):
    monkeypatch.setenv(knob, value)
    with pytest.raises(run.BenchError, match=knob):
        run.check_environment()


def test_bare_checkout_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


@pytest.mark.parametrize("conf", BENCH["configs"],
                         ids=lambda c: c["name"])
def test_warm_variants_fit_the_configuration(conf):
    cfg = json.load(open(os.path.join(ROOT, conf["file"])))
    p = cfg["polish"]
    kinds = {e[0] for e in cfg["warm_variants"]}
    assert kinds == {"align", "align_wfa", "poa_full"}
    for e in cfg["warm_variants"]:
        if e[0] == "poa_full":
            assert e[10:13] == [p["match"], p["mismatch"], p["gap"]]
            assert e[14] == int(p["trim"])


@pytest.mark.parametrize("conf", BENCH["configs"],
                         ids=lambda c: c["name"])
def test_rates_are_read_as_a_frozen_calibration(monkeypatch, tmp_path,
                                                conf):
    cfg = json.load(open(os.path.join(ROOT, conf["file"])))
    monkeypatch.setenv("RACON_TPU_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("RACON_TPU_CALIB_FREEZE", raising=False)
    rates = run.freeze_rates(cfg, 1)
    assert os.environ["RACON_TPU_CALIB_FREEZE"] == "1"
    for stage, (dev, cpu) in cfg["rates"].items():
        assert rates[stage]["source"] == "calibrated"
        assert rates[stage]["gen"] == 2
        assert rates[stage]["dev"] == dev
        assert cpu is None or rates[stage]["cpu"] == cpu
    # a store under the freeze changes nothing
    from racon_tpu.utils import calibrate
    calibrate.store_rates("align_wfa", 1, 1.0)
    assert calibrate.get_rates(
        "align_wfa", 1, 0.0, 0.0)[0] == cfg["rates"]["align_wfa"][0]


@pytest.mark.parametrize("conf", BENCH["configs"],
                         ids=lambda c: c["name"])
def test_warm_variants_go_through_the_prebuild_entry(monkeypatch, conf):
    from racon_tpu.tpu import align_pallas, poa_pallas

    cfg = json.load(open(os.path.join(ROOT, conf["file"])))
    seen = []
    monkeypatch.setattr(poa_pallas, "prewarm",
                        lambda b, d1, **kw: seen.append(("poa", b, d1)))
    monkeypatch.setattr(align_pallas, "prewarm",
                        lambda n, lq, lt, wb: seen.append(("band", n, wb)))
    monkeypatch.setattr(align_pallas, "wfa_prewarm",
                        lambda n, lq, emax: seen.append(("wfa", n, emax)))
    out = run.warm_variants(cfg)
    assert out["failed"] == [] and out["variants"] == len(seen)
    # the padded POA batch: a multiple of the windows per program
    s_win = {32: 5, 64: 4, 128: 3}
    assert all(b % s_win[d1] == 0 for k, b, d1 in seen if k == "poa")
