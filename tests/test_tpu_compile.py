"""Compile guard: the main-path kernels at real widths, compiled by the
v5e compiler for a described (not attached) chip.

Interpret-mode tests cannot see what the chip's compiler refuses: an
SMEM or VMEM overrun, a program that does not fit HBM.  Each case
compiles one kernel at the shape the dispatch policy picks for the
stock configurations and checks that a Mosaic kernel is present and
that ``pipeline_depth()`` such programs fit the device's HBM.  The
topology is described inside a fixture (only one process may load the
TPU library; describing it at import would break xdist collection).
"""

import os

import pytest

from racon_tpu.tpu import align_pallas, poa_pallas

# HBM the v5e compiler lets one program use ("Used 17.05G of 15.75G")
_HBM = int(15.75 * (1 << 30))
_LQ = 16384         # TPUPolisher.MAX_ALIGN_DIM: the widest align dim
_MEGABATCH = 256    # RACON_TPU_POA_MEGABATCH default, one device


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes)
    assert align_pallas.pipeline_depth() * total <= _HBM, total
    return total


@pytest.mark.parametrize("v,lp,d1,banded", [
    (2048, 1024, 32, False),    # w=500
    (2048, 1024, 64, False),    # w=500, >31-layer windows (30x ONT)
    (4096, 2048, 32, False),    # w=1000
    (4096, 2048, 64, False),
    (4096, 2048, 32, True),     # w=1000 with -b
])
def test_poa_full_compiles(one_chip, v, lp, d1, banded):
    import jax.numpy as jnp

    wb = poa_pallas.band_width(lp, banded)
    s_win = poa_pallas.pick_windows_per_program(v, lp, d1, wb=wb)
    krank = poa_pallas.pick_rank_unroll(v, lp, d1, wb=wb, s_win=s_win)
    assert s_win > 0
    b = poa_pallas.padded_batch(_MEGABATCH, 1, v, lp, d1, wb=wb)
    args = (_spec(one_chip, (b, d1, lp), jnp.uint8),
            _spec(one_chip, (b, d1, lp), jnp.uint8),
            _spec(one_chip, (b, d1, 8), jnp.int32),
            _spec(one_chip, (b,), jnp.int32),
            _spec(one_chip, (b,), jnp.int32))
    compiled = poa_pallas._poa_full.lower(
        *args, v, lp, d1, 16, 16, 8, 128, wb, 5, -4, -8, 1, 1, s_win,
        krank, False).compile()
    _check(compiled)


@pytest.mark.parametrize("emax", [512, 1024, 2048])
def test_wfa_compiles_at_policy_chunk(one_chip, emax):
    import jax.numpy as jnp

    per_pair = align_pallas.wfa_per_pair_bytes(_LQ, emax)
    n = align_pallas.chunk_pairs(per_pair)
    assert align_pallas.pad_pairs(n, 1, per_pair) == n
    compiled = align_pallas._wfa_call.lower(
        _spec(one_chip, (n, _LQ), jnp.uint8),
        _spec(one_chip, (n, _LQ), jnp.uint8),
        _spec(one_chip, (n,), jnp.int32),
        _spec(one_chip, (n,), jnp.int32), _LQ, emax, False).compile()
    total = _check(compiled)
    # the sizing model bounds what the compiler allocates
    assert total <= n * per_pair


@pytest.mark.parametrize("wb", [2048, 4096])
def test_banded_align_compiles_at_policy_chunk(one_chip, wb):
    import jax.numpy as jnp

    per_pair = align_pallas.per_pair_bytes(_LQ, wb)
    n = align_pallas.chunk_pairs(per_pair)
    compiled = align_pallas._align.lower(
        _spec(one_chip, (n, _LQ), jnp.uint8),
        _spec(one_chip, (n, _LQ), jnp.uint8),
        _spec(one_chip, (n,), jnp.int32),
        _spec(one_chip, (n,), jnp.int32),
        _spec(one_chip, (n, align_pallas._n_ctr(_LQ)), jnp.int32),
        _LQ, _LQ, wb, False).compile()
    _check(compiled)
