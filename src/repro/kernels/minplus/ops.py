"""Public op: bandwidth-masked min-plus relaxation (kernel or oracle).

``masked_minplus(P, lat, bw, breq)`` — signature matches the DP's move step
(``breq`` is the raw (p-1,) dataflow-edge requirement vector; the k-indexed
threshold vector is built here).  Dispatches to the Pallas TPU kernel
(interpret mode off-TPU) or the pure-jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.device import on_tpu

from . import minplus as _kernel
from . import ref as _ref

BIG = _ref.BIG


def _breq_k(breq, K):
    return jnp.concatenate(
        [jnp.full((1,), BIG), breq.astype(jnp.float32),
         jnp.full((K - 1 - breq.shape[0],), BIG)]
    )


def masked_minplus(P, lat, bw, breq, *, tiles: tuple[int, int, int] | None = None):
    """Move step: returns (C' (n,K) float32, pv (n,K) int32)."""
    K = P.shape[1]
    bq = _breq_k(breq, K)
    kw = {}
    if tiles is not None:
        kw = dict(v_tile=tiles[0], w_tile=tiles[1], k_tile=tiles[2])
    return _kernel.masked_minplus_pallas(
        P, lat, bw, bq, interpret=not on_tpu(), **kw
    )


def masked_minplus_ref(P, lat, bw, breq):
    K = P.shape[1]
    return _ref.masked_minplus_ref(P, lat, bw, _breq_k(breq, K))
