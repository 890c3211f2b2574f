"""Device-mesh scaling utilities.

The framework's parallelism is data-parallel over the leading batch axis
of fixed-shape work batches (windows / overlaps) — the TPU-native
equivalent of racon-gpu's independent per-device batch queues
(reference: src/cuda/cudapolisher.cpp:170-188,231-243, which use no
inter-device communication at all).  A 1-D mesh shards the batch axis
over ICI; there are no collectives in the hot path, and host-side
result concatenation is the only "all-gather".
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def interpret_mode() -> bool:
    """Pallas kernels run in interpret mode on non-TPU backends (the
    CPU dryrun mesh and the sharding tests).  A backend that fails to
    start raises here: it must not quietly select interpret mode."""
    return jax.devices()[0].platform != "tpu"


def shard_batch_map(fn, mesh: Mesh, n_in: int, n_out: int):
    """``shard_map`` over the 1-D batch axis with Pallas-friendly
    settings (the vma/rep output check is off: ``pallas_call``
    out_shapes carry no vma annotation)."""
    spec = P("batch")
    out = spec if n_out == 1 else (spec,) * n_out
    return shard_map(fn, mesh=mesh, in_specs=(spec,) * n_in,
                     out_specs=out, check_vma=False)


def default_mesh(max_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over all (or the first ``max_devices``) LOCAL devices.

    Local, not global: under jax.distributed the work is
    target-sharded per host (racon_tpu/parallel/multihost.py) and each
    rank's batches are host-side numpy arrays, so a mesh spanning
    another host's non-addressable chips could never be fed."""
    devices = jax.local_devices()
    if max_devices is not None:
        devices = devices[:max_devices]
    return Mesh(np.array(devices), axis_names=("batch",))


def pad_to_multiple(arr: np.ndarray, multiple: int,
                    fill) -> np.ndarray:
    """Pad the leading axis up to a multiple (mesh-divisible batches)."""
    b = arr.shape[0]
    rem = (-b) % multiple
    if rem == 0:
        return arr
    pad_block = np.full((rem,) + arr.shape[1:], fill, dtype=arr.dtype)
    return np.concatenate([arr, pad_block], axis=0)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "lq", "lt", "hw"))
def _sharded_align_impl(q, t, ql, tl, *, mesh: Mesh, lq: int, lt: int,
                        hw: int = 0):
    from racon_tpu.tpu.aligner import _align_kernel, _banded_align_kernel

    spec = P("batch")

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec, spec),
                       out_specs=spec)
    def shard_fn(q, t, ql, tl):
        if hw:
            return _banded_align_kernel(q, t, ql, tl, lq, lt, hw)
        return _align_kernel(q, t, ql, tl, lq, lt)

    return shard_fn(q, t, ql, tl)


def sharded_align(mesh: Mesh, q, t, ql, tl, *, lq: int, lt: int,
                  hw: int = 0):
    """Batched alignment sharded over the mesh batch axis.

    The batch must be divisible by the mesh size (use
    ``pad_to_multiple``); each device runs the wavefront kernel
    (banded when ``hw`` > 0) on its shard independently.
    """
    return _sharded_align_impl(q, t, ql, tl, mesh=mesh, lq=lq, lt=lt,
                               hw=hw)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "v", "l", "p", "k", "wb", "match",
                     "mismatch", "gap"))
def _sharded_poa_impl(bases, preds, nrows, sinks, seq, slen, *,
                      mesh: Mesh, v: int, l: int, p: int, k: int,
                      wb: int, match: int, mismatch: int, gap: int):
    from racon_tpu.tpu.poa import _poa_kernel, _poa_kernel_banded

    spec = P("batch")

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec,) * 6,
                       out_specs=(spec, spec))
    def shard_fn(bases, preds, nrows, sinks, seq, slen):
        if wb:
            return _poa_kernel_banded(bases, preds, nrows, sinks, seq,
                                      slen, v, l, p, k, wb, match,
                                      mismatch, gap)
        return _poa_kernel(bases, preds, nrows, sinks, seq, slen,
                           v, l, p, k, match, mismatch, gap)

    return shard_fn(bases, preds, nrows, sinks, seq, slen)


def sharded_poa(mesh: Mesh, bases, preds, nrows, sinks, seq, slen, *,
                v: int, l: int, p: int, k: int, match: int,
                mismatch: int, gap: int, wb: int = 0):
    """One batched POA layer-round sharded over the mesh batch axis.

    TPU-native analog of racon-gpu's per-device POA batch queues
    (reference: src/cuda/cudapolisher.cpp:231-243): windows are
    embarrassingly parallel, so the round's fixed-shape arrays shard on
    the leading axis with no collectives in the hot path.
    """
    return _sharded_poa_impl(bases, preds, nrows, sinks, seq, slen,
                             mesh=mesh, v=v, l=l, p=p, k=k, wb=wb,
                             match=match, mismatch=mismatch, gap=gap)
