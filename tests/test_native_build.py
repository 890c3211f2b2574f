"""The native library rebuilds from its tracked sources whenever one
of them or the host's -march target changes (racon_tpu/ops/cpu.py
``build_stamp``): a library copied from another machine, or built
before an edit to any source, is never loaded."""

import os
import shutil

import pytest

from racon_tpu.ops import cpu


@pytest.fixture
def native_copy(tmp_path, monkeypatch):
    """A copy of the native sources with a library and a stamp that
    look current, and a recorder in place of ``make``."""
    d = tmp_path / "native"
    d.mkdir()
    for name in cpu._BUILD_INPUTS:
        shutil.copy(os.path.join(cpu._NATIVE_DIR, name), d / name)
    monkeypatch.setattr(cpu, "_NATIVE_DIR", str(d))
    monkeypatch.delenv("RACON_TPU_NATIVE_LIB", raising=False)
    monkeypatch.setattr(cpu, "_march", lambda: "host-a")
    (d / "libracon_native.so").write_bytes(b"")
    (d / "libracon_native.stamp").write_text(cpu.build_stamp() + "\n")
    calls = []

    class _Done:
        returncode = 0
        stderr = ""

    def fake_run(argv, **kw):
        calls.append(argv)
        return _Done()

    monkeypatch.setattr(cpu.subprocess, "run", fake_run)
    return d, calls


def test_current_stamp_skips_the_build(native_copy):
    d, calls = native_copy
    cpu._build_library()
    assert calls == []


def _edit_poa_batch(d, monkeypatch):
    with open(d / "poa_batch.cpp", "a") as f:
        f.write("\n// edited\n")


def _edit_header(d, monkeypatch):
    with open(d / "poa_graph.hpp", "a") as f:
        f.write("\n// edited\n")


def _other_host(d, monkeypatch):
    monkeypatch.setattr(cpu, "_march", lambda: "host-b")


def _stale_stamp(d, monkeypatch):
    (d / "libracon_native.stamp").write_text("0" * 64 + "\n")


def _no_stamp(d, monkeypatch):
    os.remove(d / "libracon_native.stamp")


@pytest.mark.parametrize("change", [_edit_poa_batch, _edit_header,
                                    _other_host, _stale_stamp,
                                    _no_stamp])
def test_change_rebuilds_and_restamps(native_copy, monkeypatch,
                                      change):
    d, calls = native_copy
    change(d, monkeypatch)
    cpu._build_library()
    assert calls == [["make", "-B", "-C", str(d), "-j"]]
    assert (d / "libracon_native.stamp").read_text().strip() == \
        cpu.build_stamp()
    cpu._build_library()            # now current: no second build
    assert len(calls) == 1
