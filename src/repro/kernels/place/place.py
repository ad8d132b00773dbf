"""Pallas TPU kernel: capacity-window minimum (the BCPM place step).

    P[v, k]  = min_{j <= k, prefix[k] - prefix[j] <= cap[v]}  C[v, j]

Tiling mirrors kernels/minplus: (v, k) output blocks in VMEM; the j
reduction is materialized as a (V, K_OUT, K) candidate block (K = padded
prefix length, small) and min-reduced on the VPU.  Feasibility is computed
in-kernel from the prefix sums and per-row capacities — no (n, K, K) mask
ever touches HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.problem import BIG, EPS_CAP_F32

V_TILE = 128
K_OUT_TILE = 8

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel")
)


def _kernel(prefix_ref, prefix_out_ref, c_ref, cap_ref, p_ref, pj_ref):
    k_blk = pl.program_id(1)
    C = c_ref[...]  # (V, K)
    cap = cap_ref[...]  # (V, 1)
    prefix = prefix_ref[0, :]  # (K,)
    prefix_out = prefix_out_ref[0, :]  # (K_OUT,) = prefix[k] for this block

    K = C.shape[1]
    KO = prefix_out.shape[0]
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (1, KO, K), 2)
    k_idx = k_blk * KO + jax.lax.broadcasted_iota(jnp.int32, (1, KO, K), 1)
    block = prefix_out[None, :, None] - prefix[None, None, :]  # (1, KO, K)
    feas = (j_idx <= k_idx) & (block <= cap[:, :, None] + EPS_CAP_F32)  # (V, KO, K)
    cand = jnp.where(feas, C[:, None, :], BIG)
    p_ref[...] = jnp.min(cand, axis=2)
    pj_ref[...] = jnp.argmin(cand, axis=2).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("v_tile", "k_out_tile", "interpret"))
def place_window_pallas(C, cap, prefix, *, v_tile: int = V_TILE,
                        k_out_tile: int = K_OUT_TILE, interpret: bool = False):
    n, K = C.shape
    n_pad = -(-n // v_tile) * v_tile
    K_pad = -(-K // k_out_tile) * k_out_tile

    Cp = jnp.full((n_pad, K_pad), BIG, jnp.float32).at[:n, :K].set(C)
    capp = jnp.full((n_pad, 1), -1.0, jnp.float32).at[:n, 0].set(cap)
    # padded prefix entries get +inf so padded k columns are infeasible
    pre = jnp.full((1, K_pad), BIG, jnp.float32).at[0, :K].set(prefix)

    grid = (n_pad // v_tile, K_pad // k_out_tile)
    P, pj = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, K_pad), lambda v, k: (0, 0)),  # full prefix
            pl.BlockSpec((1, k_out_tile), lambda v, k: (0, k)),  # prefix[k]
            pl.BlockSpec((v_tile, K_pad), lambda v, k: (v, 0)),  # C rows
            pl.BlockSpec((v_tile, 1), lambda v, k: (v, 0)),  # cap
        ],
        out_specs=[
            pl.BlockSpec((v_tile, k_out_tile), lambda v, k: (v, k)),
            pl.BlockSpec((v_tile, k_out_tile), lambda v, k: (v, k)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, K_pad), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, K_pad), jnp.int32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(pre, pre, Cp, capp)
    return P[:n, :K], pj[:n, :K]
