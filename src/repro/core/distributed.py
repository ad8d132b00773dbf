"""Decentralized BCPM on a JAX device mesh (the paper's Alg. 4, SPMD-native).

The paper's constraint — "each node in the resource network is aware of the
state of its immediate neighborhood only" — is mapped onto SPMD hardware by
partitioning the resource-graph nodes across devices with ``shard_map``:

- each device owns a contiguous block of resource nodes: their capacities,
  their partial-map state rows ``C[v, :]`` and their *incoming* link columns
  ``lat[:, owned]``, ``bw[:, owned]`` (= local neighborhood knowledge);
- one relaxation superstep = local *place* step + frontier exchange
  (``all_gather`` of the placed frontier ``P`` — the bulk-synchronous
  analogue of the paper's asynchronous message flood) + local *move* step;
- termination: a psum'd ``changed`` flag inside ``lax.while_loop`` —
  the paper's quiescence detection (or first-feasible forced stop).

Message accounting matches the async algorithm: a superstep "sends" one
message per (improved frontier state, feasible outgoing neighbor) pair;
we report total and cross-device counts so the BSP engine is comparable to
``core.simulator`` in ``benchmarks/bench_messages.py``.

This module is also the production path for *placement at scale*: mapping
requests for thousands-of-node resource graphs are solved on the very pod
they will run on, with the graph state sharded — no single host ever holds
the full network state (the paper's motivating constraint).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .graph import DataflowPath, Mapping, ResourceGraph
from .leastcost import HeuristicStats, _place_step
from .problem import BIG, EPS_CAP_F32, EPS_IMPROVE, creq_prefix, finite_lat
from .reconstruct import reconstruct_mapping

@dataclasses.dataclass
class DistStats(HeuristicStats):
    messages_total: int = 0  # async-equivalent messages
    messages_cross_device: int = 0  # messages that crossed a partition
    supersteps: int = 0


def _pad_to(x: np.ndarray, n_pad: int, fill) -> np.ndarray:
    pad = [(0, n_pad - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return np.pad(x, pad, constant_values=fill)


def _local_move(P_all, lat_cols, bw_cols, breq_k):
    """C'[w,k] for owned w: min_v P_all[v,k] + lat[v,w], bw[v,w] >= breq[k-1]."""

    def one_k(args):
        bk, Pk = args  # Pk: (n_pad,)
        cand = jnp.where(bw_cols >= bk, Pk[:, None] + lat_cols, BIG)  # [v, w_loc]
        return jnp.min(cand, axis=0), jnp.argmin(cand, axis=0).astype(jnp.int32)

    Cmv_t, pv_t = jax.lax.map(one_k, (breq_k, P_all.T))
    return Cmv_t.T, pv_t.T  # (n_loc, p+1)


def _dist_body(C, par_v, par_j, msg_tot, msg_x, cap_loc, lat_cols, bw_cols,
               prefix, breq_k, out_deg, out_deg_x, axis: str):
    """One superstep, executed inside shard_map."""
    P_loc, pj_loc = _place_step(C, cap_loc, prefix)
    P_all = jax.lax.all_gather(P_loc, axis, tiled=True)  # frontier exchange
    pj_all = jax.lax.all_gather(pj_loc, axis, tiled=True)
    Cmv, pv = _local_move(P_all, lat_cols, bw_cols, breq_k)
    upd = Cmv < C - EPS_IMPROVE
    Cn = jnp.where(upd, Cmv, C)
    pj_of_pv = pj_all[pv, jnp.arange(C.shape[1])[None, :]]
    par_vn = jnp.where(upd, pv, par_v)
    par_jn = jnp.where(upd, pj_of_pv, par_j)
    # Async-message equivalence: a newly accepted map at owned node (w,k)
    # would be forwarded to every outgoing neighbor of w (one message each).
    msg_tot = msg_tot + jax.lax.psum(jnp.sum(upd * out_deg[:, None]), axis)
    msg_x = msg_x + jax.lax.psum(jnp.sum(upd * out_deg_x[:, None]), axis)
    changed = jax.lax.psum(jnp.any(upd).astype(jnp.int32), axis) > 0
    return Cn, par_vn, par_jn, msg_tot, msg_x, changed


def leastcost_shard_map(
    rg: ResourceGraph,
    df: DataflowPath,
    *,
    mesh: Optional[Mesh] = None,
    validate: bool = True,
    max_rounds: Optional[int] = None,
) -> tuple[Optional[Mapping], DistStats]:
    """LeastCostMap with the resource graph partitioned over a device mesh."""
    axis = "nodes"
    if mesh is None:
        mesh = jax.make_mesh((jax.device_count(),), (axis,))
    D = mesh.devices.size
    n, p = rg.n, df.p
    n_pad = -(-n // D) * D
    stats = DistStats()

    lat_p = np.full((n_pad, n_pad), BIG, np.float32)
    lat_p[:n, :n] = finite_lat(rg)
    bw_p = np.zeros((n_pad, n_pad), np.float32)
    bw_p[:n, :n] = rg.bw
    cap_p = _pad_to(rg.cap.astype(np.float32), n_pad, 0.0)
    prefix = creq_prefix(df).astype(np.float32)
    breq_k = np.concatenate([[BIG], df.breq, [BIG]]).astype(np.float32)
    finite_edge = np.isfinite(rg.lat) & ~np.eye(n, dtype=bool)
    out_deg = _pad_to(finite_edge.sum(1).astype(np.int32), n_pad, 0)
    owner = np.arange(n_pad) // (n_pad // D)
    cross = finite_edge & (owner[:n, None] != owner[None, :n])
    out_deg_x = _pad_to(cross.sum(1).astype(np.int32), n_pad, 0)

    C0 = np.full((n_pad, p + 1), BIG, np.float32)
    C0[df.src, 0] = 0.0
    pv0 = np.full((n_pad, p + 1), -1, np.int32)
    pj0 = np.full((n_pad, p + 1), -1, np.int32)
    T = max_rounds or max(n - 1, 1)

    row = NamedSharding(mesh, P(axis))
    col = NamedSharding(mesh, P(None, axis))
    rep = NamedSharding(mesh, P())

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(None, axis), P(None, axis),
                  P(), P(), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P(), P()),
    )
    def run(C, pv, pj, cap_loc, lat_cols, bw_cols, prefix, breq_k, out_deg, out_deg_x):
        def cond(s):
            t, _C, _pv, _pj, mt, mx, changed = s
            return (t < T) & changed

        def body(s):
            t, C, pv, pj, mt, mx, _ = s
            C, pv, pj, mt, mx, changed = _dist_body(
                C, pv, pj, mt, mx, cap_loc, lat_cols, bw_cols,
                prefix, breq_k, out_deg, out_deg_x, axis,
            )
            return t + 1, C, pv, pj, mt, mx, changed

        t, C, pv, pj, mt, mx, _ = jax.lax.while_loop(
            cond, body,
            (jnp.int32(0), C, pv, pj, jnp.float32(0), jnp.float32(0), jnp.bool_(True)),
        )
        return C, pv, pj, mt, jnp.stack([mx, t.astype(jnp.float32)])

    args = [
        jax.device_put(jnp.asarray(C0), row),
        jax.device_put(jnp.asarray(pv0), row),
        jax.device_put(jnp.asarray(pj0), row),
        jax.device_put(jnp.asarray(cap_p), row),
        jax.device_put(jnp.asarray(lat_p), col),
        jax.device_put(jnp.asarray(bw_p), col),
        jax.device_put(jnp.asarray(prefix), rep),
        jax.device_put(jnp.asarray(breq_k), rep),
        jax.device_put(jnp.asarray(out_deg).astype(jnp.float32), row),
        jax.device_put(jnp.asarray(out_deg_x).astype(jnp.float32), row),
    ]
    C, par_v, par_j, msg_tot, mx_t = jax.jit(run)(*args)
    C = np.asarray(C)[:n]
    par_v, par_j = np.asarray(par_v)[:n], np.asarray(par_j)[:n]
    stats.messages_total = int(msg_tot)
    stats.messages_cross_device = int(np.asarray(mx_t)[0])
    stats.supersteps = stats.rounds = int(np.asarray(mx_t)[1])
    stats.max_set_size = int(np.sum(C < BIG / 2))

    # finish: min over j<p with capacity for the tail on dst
    feas = (np.arange(p + 1) < p) & (
        prefix[p] - prefix <= float(rg.cap[df.dst]) + EPS_CAP_F32
    )
    final = np.where(feas, C[df.dst], BIG)
    best_j = int(np.argmin(final))
    m = reconstruct_mapping(
        rg, df, par_v, par_j, float(final[best_j]), best_j,
        validate=validate, stats=stats,
    )
    return m, stats
