"""The chip smoke's refusals (chip_smoke.py): it prints a result only
after a polish ran on a TPU, so without one -- or without the repo, or
with a knob that moves work off the device engines -- it exits
non-zero and prints no result line."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


_KNOBS = ("RACON_TPU_NO_PALLAS", "RACON_TPU_PALLAS_INTERPRET",
          "RACON_TPU_PALLAS_ALIGN", "RACON_TPU_WFA")


def _run(cwd, env_extra=None):
    # other tests in this worker may have left a knob exported
    env = {k: v for k, v in os.environ.items()
           if k not in _KNOBS and k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_fails_without_a_tpu():
    proc = _run(REPO)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_fails_without_the_repo(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = _run(str(tmp_path))
    _no_result(proc)
    assert "racon-tpu checkout" in proc.stderr


@pytest.mark.parametrize("name,val", list(zip(_KNOBS,
                                               ("1", "1", "0", "0"))))
def test_refuses_knobs_that_leave_the_device(name, val):
    proc = _run(REPO, {name: val})
    _no_result(proc)
    assert name in proc.stderr


def test_wrapper_parent_never_imports_jax():
    # one process per chip: the wrapper's parent runs its CLI children
    # one after another, each of which claims the chip, so the parent
    # itself must never load JAX
    code = ("import sys, racon_tpu.tools.wrapper, racon_tpu.serve.client;"
            "sys.exit('jax' in sys.modules)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
