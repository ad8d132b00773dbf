"""The benchmark's networks, made from a configuration's parameters.

The yardstick owns the network it hands the system: the arrays are made
here, from the configuration file, and the same arrays feed both the plane
under test and the reference.  ``region_tree`` draws exactly what
``repro.core.topology.region_tree`` draws for the same arguments (the
bring-up run's network), so numbers stay comparable across the two.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Network:
    """``cap`` (n,), ``bw`` (n, n) and ``lat`` (n, n) float32 as handed to
    the plane (``lat`` is inf where there is no link, 0 on the diagonal),
    and ``leaf_of`` (n,) the leaf region of each node."""

    cap: np.ndarray
    bw: np.ndarray
    lat: np.ndarray
    leaf_of: np.ndarray

    @property
    def n(self) -> int:
        return int(self.cap.shape[0])

    @property
    def leaves(self) -> int:
        return int(self.leaf_of.max()) + 1

    @property
    def leaf_nodes(self) -> int:
        return self.n // self.leaves


def region_tree(levels: int, branching: int, leaf_nodes: int, *,
                cap_range=(2.0, 10.0), bw_range=(10.0, 100.0),
                lat_intra: float = 1.0, lat_level: float = 5.0,
                gateway_bw_scale: float = 4.0, seed: int = 0) -> Network:
    """``branching ** levels`` fully meshed leaves of ``leaf_nodes`` nodes;
    at tree level ``l`` sibling subtrees are joined all-to-all through one
    gateway node each (leaf node ``(l - 1) % leaf_nodes`` of the subtree's
    first leaf) by links of latency ``lat_level * l`` and
    ``gateway_bw_scale`` times the leaf bandwidth draw."""
    rng = np.random.default_rng(seed)
    leaves = branching ** levels
    k = leaf_nodes
    n = leaves * k
    cap = rng.uniform(*cap_range, size=n).astype(np.float32)
    bw = np.zeros((n, n), np.float32)
    lat = np.full((n, n), np.inf, np.float32)
    np.fill_diagonal(lat, 0.0)

    def link(u, v, latency, scale=1.0):
        b = scale * float(rng.uniform(*bw_range))
        bw[u, v] = bw[v, u] = b
        lat[u, v] = lat[v, u] = latency

    for leaf in range(leaves):
        base = leaf * k
        for i in range(k):
            for j in range(i + 1, k):
                link(base + i, base + j, lat_intra)
    for lvl in range(1, levels + 1):
        sub = branching ** (lvl - 1)
        block = sub * branching
        gw = (lvl - 1) % k
        for start in range(0, leaves, block):
            reps = [(start + c * sub) * k + gw for c in range(branching)]
            for i in range(branching):
                for j in range(i + 1, branching):
                    link(reps[i], reps[j], lat_level * lvl, gateway_bw_scale)
    leaf_of = np.repeat(np.arange(leaves, dtype=np.int64), k)
    return Network(cap, bw, lat, leaf_of)


GENERATORS = {"region_tree": region_tree}


def build(spec: dict) -> Network:
    """The network a configuration's ``network`` entry describes."""
    spec = dict(spec)
    gen = GENERATORS[spec.pop("generator")]
    for key in ("cap_range", "bw_range"):
        if key in spec:
            spec[key] = tuple(spec[key])
    return gen(**spec)
