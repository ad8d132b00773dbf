"""Plain reference: LeastCostMap on a residual network, and the ledger.

Written from the paper's definition (LeastCostMap, §3.4.1) and the
placement rules the configuration states, with numpy alone: it imports
nothing of the program and reads nothing the program made except the
placements it is asked to check.

LeastCostMap is a shortest path over states ``(v, j)``: "arrived at node
v with the first j operators placed".  From an arrival the request places
a contiguous block of operators ``j..k-1`` on ``v`` if their summed demand
fits the node's residual capacity (``k == j`` passes through), then moves
along a live link ``(v, w)`` whose residual bandwidth carries dataflow
edge ``k-1``, paying the link's latency.  Operator 0 sits on the source
and the last block on the destination.  The relaxation below runs these
supersteps (place, then move) to a fixpoint; ``max_supersteps`` stops it
early, which is the control: a relaxation cut short.
"""
from __future__ import annotations

import math

import numpy as np

from .network import Network

EPS = 1e-6  # slack on capacity and bandwidth comparisons (float32 inputs)


class Reference:
    """Edge lists of one network plus a residual ledger over it."""

    def __init__(self, net: Network):
        n = net.n
        lat = net.lat.astype(np.float64)
        link = np.isfinite(lat) & ~np.eye(n, dtype=bool)
        eu, ev = np.nonzero(link)
        self.n = n
        self.eu, self.ev = eu, ev  # grouped by source
        self.elat = lat[self.eu, self.ev]
        # out-links of every node: edges ``first[v]:first[v + 1]``
        self.first = np.searchsorted(self.eu, np.arange(n + 1))
        self.edge = {(int(u), int(v)): i
                     for i, (u, v) in enumerate(zip(self.eu, self.ev))}
        self.lat = lat
        self.base_cap = net.cap.astype(np.float64)
        self.base_bw = net.bw.astype(np.float64)[self.eu, self.ev]
        self.cap = self.base_cap.copy()
        self.bw = self.base_bw.copy()
        self.up = np.ones(n, bool)

    # -- ledger ---------------------------------------------------------------

    def loads(self, creq, breq, assign, route):
        """Compute per node and bandwidth per link of one placement: a hop
        carries the dataflow edge that leaves the last operator placed on
        or before it."""
        nodes: dict = {}
        for i, v in enumerate(assign):
            nodes[int(v)] = nodes.get(int(v), 0.0) + float(creq[i])
        links: dict = {}
        pos = 0
        p = len(assign)
        for u, v in zip(route[:-1], route[1:]):
            while pos + 1 < p and assign[pos + 1] == u:
                pos += 1
            key = (int(u), int(v))
            links[key] = links.get(key, 0.0) + float(breq[min(pos, p - 2)])
        return nodes, links

    def apply(self, creq, breq, assign, route, sign: float) -> None:
        """Take (``sign=-1``) or give back (``+1``) a placement's load.  A
        hop over no link (an invalid placement, already counted) holds
        nothing."""
        nodes, links = self.loads(creq, breq, assign, route)
        for v, c in nodes.items():
            self.cap[v] += sign * c
        for e, b in links.items():
            if e in self.edge:
                self.bw[self.edge[e]] += sign * b

    def invalid(self, creq, breq, src, dst, assign, route, cost):
        """Why a placement breaks the stated rules on the current residual,
        or None: pinned endpoints, a simple route over live links that
        visits the operators' nodes in order, aggregate node capacity,
        link bandwidth, and a cost equal to the route's latency."""
        p = len(creq)
        assign = [int(v) for v in assign]
        route = [int(v) for v in route]
        if len(assign) != p or assign[0] != src or assign[-1] != dst:
            return "endpoints"
        if not route or route[0] != src or route[-1] != dst:
            return "route endpoints"
        if len(set(route)) != len(route):
            return "route revisits a node"
        hosts = [v for i, v in enumerate(assign) if i == 0 or assign[i - 1] != v]
        it = iter(route)
        if not all(any(w == v for w in it) for v in hosts):
            return "route skips an operator's node"
        if not all(self.up[v] for v in route):
            return "route uses a failed node"
        for u, v in zip(route[:-1], route[1:]):
            if (u, v) not in self.edge:
                return f"no link ({u},{v})"
        pos = 0
        for u in route[:-1]:
            while pos + 1 < p and assign[pos + 1] == u:
                pos += 1
            if pos >= p - 1:
                return "route continues past the sink"
        nodes, links = self.loads(creq, breq, assign, route)
        for v, c in nodes.items():
            if c > self.cap[v] + EPS:
                return f"node {v} over capacity"
        for e, b in links.items():
            if b > self.bw[self.edge[e]] + EPS:
                return f"link {e} over bandwidth"
        expect = sum(float(self.lat[u, v])
                     for u, v in zip(route[:-1], route[1:]))
        if abs(expect - float(cost)) > EPS * max(1.0, expect):
            return f"cost {cost} is not the route's latency {expect}"
        return None

    # -- LeastCostMap -----------------------------------------------------------

    def least_cost(self, creq, breq, src: int, dst: int,
                   max_supersteps: int | None = None) -> float:
        """Least placement cost of one request on the current residual
        (``inf`` when none fits)."""
        n, p = self.n, len(creq)
        if not (self.up[src] and self.up[dst]):
            return math.inf
        prefix = np.concatenate([[0.0], np.cumsum(np.asarray(creq, np.float64))])
        cap = np.where(self.up, self.cap, -np.inf)
        live = self.up[self.eu] & self.up[self.ev]
        carry = [live & (self.bw + EPS >= float(breq[k - 1]))
                 for k in range(1, p)]
        arrive = np.full((p, n), np.inf)
        arrive[0, src] = 0.0
        placed = np.full((p, n), np.inf)
        rounds = max_supersteps if max_supersteps is not None else n
        for _ in range(rounds):
            fresh = np.full((p, n), np.inf)
            for k in range(1, p):
                for j in range(k + 1):
                    ok = prefix[k] - prefix[j] <= cap + EPS
                    np.minimum(fresh[k], np.where(ok, arrive[j], np.inf),
                               out=fresh[k])
            changed = False
            for k in range(1, p):
                # only nodes whose placed cost fell can improve a neighbour
                moved = np.flatnonzero(fresh[k] < placed[k])
                if not moved.size:
                    continue
                lo, hi = self.first[moved], self.first[moved + 1]
                count = hi - lo
                edges = (np.arange(count.sum())
                         - np.repeat(np.cumsum(count) - count, count)
                         + np.repeat(lo, count))
                edges = edges[carry[k - 1][edges]]
                cand = fresh[k, self.eu[edges]] + self.elat[edges]
                before = arrive[k].copy()
                np.minimum.at(arrive[k], self.ev[edges], cand)
                changed |= bool((arrive[k] < before).any())
            placed = fresh
            if not changed:
                break
        tail = prefix[p] - prefix[:p] <= cap[dst] + EPS
        return float(np.min(np.where(tail, arrive[:, dst], np.inf)))
