"""Compiles for a described TPU v5e chip: what interpret mode cannot check.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these run on a CPU-only host.  They guard that
the batched Pallas kernel lowers at its default tiles for K_pad 8 and 16
(the (8, 128) block rule), and that the main-path DPs compile in seconds.
Nothing runs: results are checked by the interpret-mode and ref tests.
"""
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

COMPILE_BUDGET_S = 60.0


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs in /tmp
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _request_tensors(B, n, p, s):
    i32 = jnp.int32
    return dict(cap=_spec((n,), s), bw=_spec((n, n), s), lat=_spec((n, n), s),
                prefix=_spec((B, p + 1), s), breq=_spec((B, p - 1), s),
                src=_spec((B,), s, i32), dst=_spec((B,), s, i32),
                p_eff=_spec((B,), s, i32))


def _compile(lowered):
    t0 = time.perf_counter()
    compiled = lowered.compile()
    secs = time.perf_counter() - t0
    assert secs < COMPILE_BUDGET_S, f"compile took {secs:.1f}s"
    return compiled


@pytest.mark.parametrize("p", [5, 12])
def test_batched_kernel_lowers_at_default_tiles(one_chip, p):
    """One superstep at n_pad=1024, B=8: K_pad 8 (p=5) and 16 (p=12)."""
    from repro.kernels.minplus import batched as bk

    B, n = 8, 1024
    K_pad = -(-(p + 1) // bk.K_ALIGN) * bk.K_ALIGN
    s, i32 = one_chip, jnp.int32
    args = (_spec((B, n, K_pad), s), _spec((B, n, K_pad), s, i32),
            _spec((B, n, K_pad), s, i32), _spec((n, n), s), _spec((n, n), s),
            _spec((n,), s), _spec((B, K_pad), s), _spec((B, K_pad), s))
    compiled = _compile(bk.batched_superstep_pallas.lower(*args))
    assert "tpu_custom_call" in compiled.as_text()


def test_kernel_dp_compiles(one_chip):
    """The whole use_kernel=True DP (place, kernel, while loop, readout)."""
    from repro.core.leastcost import _leastcost_dp_batched

    B, n, p = 8, 1024, 5
    lowered = _leastcost_dp_batched.lower(
        _request_tensors(B, n, p, one_chip), B=B, n=n, p=p,
        max_rounds=n - 1, impl="pallas")
    assert "tpu_custom_call" in _compile(lowered).as_text()


def test_vmapped_dp_compiles(one_chip):
    """The default admission DP at n=1024 and the largest batch bucket."""
    from repro.core.leastcost import _vmapped_dp

    B, n, p = 32, 1024, 5
    _compile(_vmapped_dp(n, p, n - 1).lower(_request_tensors(B, n, p,
                                                             one_chip)))
