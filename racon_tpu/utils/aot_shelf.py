"""Persisted ``jax.export`` artifacts: cold starts skip retracing.

The persistent XLA compilation cache already amortises COMPILES across
processes, but every fresh process still pays 1-2 s of Python TRACING
per kernel variant, serialized by the GIL -- on the sample workload
that tracing is most of the cold-vs-warm gap (the reference's CUDA
kernels are build-time compiled, so its runs are always "warm").  This
shelf serializes each variant's exported StableHLO next to the XLA
cache on first use; later processes deserialize (~0.1 s) instead of
retracing, and the compile underneath is a cache load.

Artifacts are keyed by the kernel source hash, jax version, platform
and the full static configuration, so a code change rotates the key
and can never replay a stale kernel.  Any failure falls back to the
plain traced path -- the shelf is an accelerator, not a dependency.
"""

from __future__ import annotations

import hashlib
import os
import threading

from racon_tpu.obs import trace as obs_trace

_mem: dict = {}
_salts: dict = {}
_recorded: set = set()
# first-contact outcome per variant key_parts: "hit" (deserialized
# from the shelf), "miss" (had to export+compile), "fallback" (export
# unsupported or artifact failed -> plain traced path).  Anything but
# "hit" on a cold run is start-up latency the prebuild manifest should
# have covered -- bench.py prints this list after its cold leg so the
# residual cold-start gap stays diagnosable (VERDICT next #4).
_contact: dict = {}
_lock = threading.Lock()


def contacts() -> dict:
    with _lock:
        return dict(_contact)


def misses() -> list:
    """Variant keys whose first contact this process was NOT a shelf
    hit (each cost a foreground trace+compile)."""
    with _lock:
        return [k for k, v in _contact.items() if v != "hit"]


def _log_contact(key_parts: tuple, outcome: str) -> None:
    with _lock:
        if key_parts in _contact:
            return
        _contact[key_parts] = outcome
    # process-wide first-contact counters (aot_shelf_hit/miss/fallback):
    # shelf state is per process, not per polish, so these live in the
    # GLOBAL registry and surface in the run report's "process" section
    from racon_tpu.obs.metrics import REGISTRY
    REGISTRY.add(f"aot_shelf_{outcome}")
    # decision record (r16): which kernel variant was selected and
    # whether the shelf served it — `racon-tpu explain` attributes
    # cold-start walls to these first contacts
    from racon_tpu.obs.decision import DECISIONS
    DECISIONS.record("shelf", outcome=outcome,
                     variant="/".join(str(p) for p in key_parts))
    import sys
    print(f"[racon_tpu::aot_shelf] {outcome}: "
          f"{'/'.join(str(p) for p in key_parts)}", file=sys.stderr)

# bump when kernel-relevant code OUTSIDE the keyed source file changes
# behavior (the key hashes only the caller's own source file; helpers
# that migrate into imported modules would otherwise replay stale
# exports)
_SHELF_VERSION = 1


def _shelf_dir():
    from racon_tpu.utils.xla_cache import cache_root

    root = cache_root()
    if root is None:
        return None
    return os.path.join(root, "aot")


def _source_salt(src_file: str) -> str:
    with _lock:
        salt = _salts.get(src_file)
        if salt is None:
            try:
                with open(src_file, "rb") as f:
                    salt = hashlib.sha1(f.read()).hexdigest()[:12]
            except OSError:
                salt = "nosrc"
            _salts[src_file] = salt
        return salt


def enabled() -> bool:
    """Shelving is for real-TPU cold starts; interpret-mode/CPU test
    paths keep the plain traced path (their compiles are cheap and
    their artifacts would pollute the shelf)."""
    if os.environ.get("RACON_TPU_NO_AOT_SHELF"):
        return False
    if os.environ.get("RACON_TPU_PALLAS_INTERPRET") == "1":
        return False
    try:
        import jax
        return jax.devices()[0].platform == "tpu"
    except Exception:
        return False


def _record_manifest(key_parts: tuple) -> None:
    """Append a variant's key_parts to the shelf manifest (dedup).

    The manifest is what ``python -m racon_tpu.prebuild`` replays to
    build every previously-seen kernel variant at install time -- the
    analog of the reference's build-time CUDA kernel compilation
    (SURVEY.md §2.3 L4g): after a code change or on a fresh cache,
    one untimed prebuild pass re-traces everything instead of the
    first polish paying each variant serially."""
    with _lock:
        if key_parts in _recorded:   # hot path: one set probe per call
            return
        _recorded.add(key_parts)
    d = _shelf_dir()
    if d is None:
        return
    import json
    path = os.path.join(d, "manifest.json")
    with _lock:
        try:
            with open(path) as f:
                entries = json.load(f)
        except (OSError, ValueError):
            entries = []
        entry = list(key_parts)
        if entry in entries:
            return
        entries.append(entry)
        try:
            os.makedirs(d, exist_ok=True)
            tmp = path + f".tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(entries, f, indent=0)
            os.replace(tmp, path)
        except OSError:
            pass  # the manifest is an optimization, never a failure


def call(key_parts: tuple, src_file: str, build_fn, args: tuple):
    """Invoke ``build_fn(*args)`` through a shelved export when
    possible.  ``build_fn`` must be a pure jit-able function of
    ``args`` with all static configuration closed over (and captured
    in ``key_parts``)."""
    if not enabled() or _shelf_dir() is None:
        return build_fn(*args)
    _record_manifest(key_parts)
    import jax

    key = hashlib.sha1(
        repr((key_parts, _source_salt(src_file), _SHELF_VERSION,
              jax.__version__,
              jax.devices()[0].platform)).encode()).hexdigest()[:24]
    with _lock:
        fn = _mem.get(key)
    if fn is not None:
        try:
            return fn(*args)
        except Exception:
            # a shelved artifact that stopped working (e.g. a libtpu
            # change the key's jax version does not capture) must not
            # take the polish down: fall back to the traced path
            with _lock:
                _mem[key] = build_fn
            return build_fn(*args)
    # first contact in this process: a shelf load or an export, and
    # the new jit's first call -- the span names a compile that lands
    # inside a traced window
    with obs_trace.span("racon_tpu.compile", cat="compile",
                        args={"variant": "/".join(
                            str(p) for p in key_parts)}):
        return _first_contact(key, key_parts, build_fn, args)


def _first_contact(key: str, key_parts: tuple, build_fn, args: tuple):
    import jax
    from jax import export as jexport

    path = os.path.join(_shelf_dir(), key + ".jexp")
    exp = None
    if os.path.exists(path):
        try:
            with open(path, "rb") as f:
                exp = jexport.deserialize(f.read())
            _log_contact(key_parts, "hit")
        except Exception:
            try:
                os.remove(path)
            except OSError:
                pass
            exp = None
    if exp is None:
        try:
            exp = jexport.export(jax.jit(build_fn))(*args)
            _log_contact(key_parts, "miss")
            blob = exp.serialize()
            os.makedirs(_shelf_dir(), exist_ok=True)
            tmp = path + f".tmp{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        except Exception:
            # export unsupported for this function/config: remember the
            # plain path for this process and move on
            _log_contact(key_parts, "fallback")
            with _lock:
                _mem[key] = build_fn
            return build_fn(*args)
    try:
        fn = jax.jit(exp.call)
        out = fn(*args)
        # surface async device-side failures of a stale artifact NOW,
        # while the fallback below can still retrace (JAX dispatch is
        # async; without this the error fires later at collect(),
        # outside any try) -- one-time cost on first use only
        jax.block_until_ready(out)
    except Exception:
        try:
            os.remove(path)
        except OSError:
            pass
        with _lock:
            _mem[key] = build_fn
            _contact[key_parts] = "fallback"   # stale artifact retraced
        return build_fn(*args)
    with _lock:
        _mem[key] = fn
    return out
