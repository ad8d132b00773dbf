"""Open-loop request streams, timed in seconds, from a traffic mix file.

The mix is ``benchmarks/bench_trace.build_trace``'s, re-timed from pump
rounds to seconds: linear dataflows of p operators with compute demands
U(creq) (endpoints 0) and bandwidth demands U(breq), 80/15/5
leaf/block/anywhere endpoint locality, Zipf-skewed tenants, preemption
classes, exponential holds, and one correlated failure of co-located nodes
that is restored a few seconds later.  Arrivals are Poisson at a fixed
rate, or Pareto-modulated bursts per epoch (``build_trace``'s burst factor,
shape 2.5 capped at 8x) with the same mean rate.  A mix may add a
``backlog``: that many requests due at the window's start, a burst that
arrived while the plane was busy, so that a plane above its capacity has a
full queue from the first pump on.

Every seed gets the same work in another order.  The mix's own
``master_seed`` fixes the arrival times (one sample path of the arrival
process) and the multiset of request shapes; the run's seed only deals the
shapes out over the arrivals in another order and draws endpoints and the
failing leaf.  So runs differ by which request comes when and where it
goes, not by how much work arrives or when, nor by how much the failure
displaces (``build``).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from .network import Network


@dataclasses.dataclass(frozen=True)
class Request:
    """One request of the stream.  ``due`` is seconds from the window's
    start (``-inf`` for the standing set); ``hold`` counts from ``due``
    (from the window's start for the standing set)."""

    due: float
    tenant: str
    klass: int
    creq: np.ndarray
    breq: np.ndarray
    src: int
    dst: int
    hold: float

    @property
    def p(self) -> int:
        return int(self.creq.shape[0])


@dataclasses.dataclass(frozen=True)
class Schedule:
    standing: list
    arrivals: list  # sorted by due
    fail_at: float  # seconds into the window; inf when the mix has no churn
    restore_at: float
    fail_nodes: tuple


def _shapes(mix: dict, count: int, rng: np.random.Generator) -> list:
    """Request shapes without endpoints: (tenant, klass, creq, breq, hold,
    locality class)."""
    tenants = mix["tenants"]
    w = 1.0 / np.arange(1, len(tenants) + 1) ** float(mix["tenant_zipf"])
    loc = mix["locality"]
    kinds = ("leaf", "block", "any")
    probs = np.array([loc[k] for k in kinds], np.float64)
    out = []
    for _ in range(count):
        p = int(rng.integers(mix["p"][0], mix["p"][1] + 1))
        creq = rng.uniform(*mix["creq"], size=p).astype(np.float32)
        creq[0] = creq[-1] = 0.0
        breq = rng.uniform(*mix["breq"], size=p - 1).astype(np.float32)
        out.append((
            tenants[int(rng.choice(len(tenants), p=w / w.sum()))],
            int(rng.integers(mix["classes"])),
            creq, breq,
            float(rng.exponential(mix["hold_mean_s"])),
            kinds[int(rng.choice(3, p=probs / probs.sum()))],
        ))
    return out


def _arrival_times(arr: dict, seconds: float,
                   master: np.random.Generator) -> np.ndarray:
    backlog = np.zeros(int(arr.get("backlog", 0)), np.float64)
    return np.concatenate([backlog, _process_times(arr, seconds, master)])


def _process_times(arr: dict, seconds: float,
                   master: np.random.Generator) -> np.ndarray:
    rate = float(arr["rate_per_s"])
    if arr["process"] == "poisson":
        gaps = []
        total = 0.0
        while True:
            g = float(master.exponential(1.0 / rate))
            if total + g >= seconds:
                break
            total += g
            gaps.append(g)
        return np.cumsum(np.asarray(gaps, np.float64))
    if arr["process"] == "pareto_bursts":
        epoch = float(arr["epoch_s"])
        n_ep = max(1, int(seconds // epoch))
        burst = np.minimum(1.0 + master.pareto(float(arr["shape"]), n_ep),
                           float(arr["cap"]))
        burst /= burst.mean()
        epochs = [np.sort(master.uniform(0.0, epoch,
                                         int(master.poisson(rate * b * epoch))))
                  for b in burst]
        return np.concatenate([i * epoch + t for i, t in enumerate(epochs)])
    raise ValueError(f"unknown arrival process {arr['process']!r}")


def _pick(rng: np.random.Generator, lo: int, count: int, avoid) -> int:
    """A leaf of ``[lo, lo + count)``, never ``avoid``."""
    if avoid is None or not lo <= avoid < lo + count:
        return lo + int(rng.integers(count))
    i = lo + int(rng.integers(count - 1))
    return i + (i >= avoid)


def _endpoints(net: Network, kind: str, block: int, rng: np.random.Generator,
               avoid=None, pin=None) -> tuple[int, int]:
    """Source and destination of one request.  ``avoid`` is a leaf no
    endpoint may lie in; ``pin`` is ``(leaf, nodes)``: the source is one
    of that leaf's first ``nodes`` nodes."""
    k = net.leaf_nodes
    if pin is not None:
        leaf, nodes = pin
        src = leaf * k + int(rng.integers(nodes))
    else:
        leaf = _pick(rng, 0, net.leaves, avoid)
        src = leaf * k + int(rng.integers(k))
    if kind == "leaf":
        dleaf = leaf
    elif kind == "block":
        dleaf = _pick(rng, (leaf // block) * block, block, avoid)
    else:
        dleaf = _pick(rng, 0, net.leaves, avoid)
    dst = dleaf * k + int(rng.integers(k))
    if dst == src:
        dst = dleaf * k + (src - dleaf * k + 1) % k
    return src, dst


def build(mix: dict, net: Network, *, seconds: float, seed: int,
          standing: int = 0) -> Schedule:
    """The whole stream of one run: ``standing`` requests to pre-load, the
    arrivals due in ``[0, seconds)``, and the failure burst.

    The failure does the same work on every seed.  It takes the first
    ``nodes`` nodes of a leaf that the seed draws among those that are not
    the first of their block (whose nodes carry the gateways of the tree's
    upper levels, and so other leaves' routes).  The mix's first
    ``pinned`` arrivals, with the first ``pinned`` shapes, have their
    source on a failing node; no other request has an endpoint in that
    leaf.  So the failure displaces those requests, and only them."""
    master = np.random.default_rng(int(mix["master_seed"]))
    run = np.random.default_rng(int(seed))
    times = _arrival_times(mix["arrivals"], float(seconds), master)
    shapes = _shapes(mix, len(times), master)
    standing_shapes = _shapes(
        mix, standing, np.random.default_rng([int(mix["master_seed"]), 1]))
    block = int(mix["block_leaves"])
    churn = mix.get("churn")
    k = net.leaf_nodes
    if churn:
        # the i-th leaf that is not the first of its block
        i = int(run.integers(net.leaves // block * (block - 1)))
        leaf = i // (block - 1) * block + i % (block - 1) + 1
        nodes = tuple(leaf * k + i for i in range(min(int(churn["nodes"]), k)))
        pin = (leaf, len(nodes))
        pinned = min(int(churn.get("pinned", 0)), len(shapes))
        fail_at = float(churn["fail_at"]) * seconds
        restore_at = fail_at + float(churn["down_s"])
    else:
        leaf, nodes, pin, pinned = None, (), None, 0
        fail_at, restore_at = math.inf, math.inf

    def make(due, shape, pin=None):
        tenant, klass, creq, breq, hold, kind = shape
        src, dst = _endpoints(net, kind, block, run, avoid=leaf, pin=pin)
        return Request(due, tenant, klass, creq, breq, src, dst, hold)

    order = np.concatenate([np.arange(pinned), pinned + run.permutation(
        len(shapes) - pinned)]).astype(int)
    arrivals = [make(float(t), shapes[i], pin if j < pinned else None)
                for j, (t, i) in enumerate(zip(times, order))]
    pre = [make(-math.inf, s) for s in standing_shapes]
    return Schedule(pre, arrivals, fail_at, restore_at, nodes)
