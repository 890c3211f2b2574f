"""Placement of JAX's persistent compilation cache
(racon_tpu/utils/xla_cache.py): ``JAX_COMPILATION_CACHE_DIR`` when it
is set, else the fixed ``<checkout>/.jax_cache``; no other directory
is ever configured."""

import os

import jax
import pytest

from racon_tpu.utils import xla_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_default_is_the_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert xla_cache.compilation_cache_dir() == \
        os.path.join(REPO, ".jax_cache")


@pytest.mark.parametrize("from_env", [True, False])
def test_enable_configures_exactly_one_directory(monkeypatch, tmp_path,
                                                 from_env):
    checkout = tmp_path / "checkout"
    monkeypatch.setattr(xla_cache, "_CHECKOUT", str(checkout))
    monkeypatch.setattr(xla_cache, "_enabled", False)
    if from_env:
        want = str(tmp_path / "from_env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        want = str(checkout / ".jax_cache")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: updates.__setitem__(name, val))
    xla_cache.enable_compilation_cache()
    assert updates["jax_compilation_cache_dir"] == want
    assert os.path.isdir(want)
    # the racon cache root (RACON_TPU_CACHE_DIR) no longer moves it
    assert not any(isinstance(v, str) and v != want
                   for v in updates.values())
    xla_cache.enable_compilation_cache()     # idempotent
    assert len([k for k in updates if k.endswith("cache_dir")]) == 1
