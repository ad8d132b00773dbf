"""Golden replay of the regional plane's decisions.

Each case drives one plane set-up through a seeded, fuzzed sequence of
submit / pump / release / fail / restore / defrag calls and fingerprints
the plane after every step: the handles each call returned, the ticket
ledger, the spanning counters, the 2PC and gossip message counts, and a
hash of every leaf placer's tickets and residual arrays.  The fingerprints
in ``data/golden_planes.json`` were recorded from the implementation that
kept the flat and the hierarchical broker as two classes, so a refactor of
the broker must replay them bit for bit.

Regenerate (only for a change that is meant to alter decisions) with
``PYTHONPATH=src python tests/test_golden_replay.py``.
"""
import hashlib
import json
import pathlib

import numpy as np
import pytest

from repro.core import DataflowPath, random_dataflow, region_tree, waxman
from repro.service import ControlPlane, FairSharePolicy

GOLDEN = pathlib.Path(__file__).with_name("data") / "golden_planes.json"
SEEDS = (0, 1, 2)
STEPS = 80


def _plane(setup: str, seed: int):
    kw = dict(micro_batch=6, max_attempts=3, seed=seed,
              policy=FairSharePolicy(slack=0.4), method="leastcost_python")
    if setup == "regions3-k1":
        rg = waxman(16, seed=4)
        cp = ControlPlane(rg, regions=3, chain_k=1, **kw)
    elif setup == "regions3-k2":
        rg = waxman(16, seed=4)
        cp = ControlPlane(rg, regions=3, chain_k=2, **kw)
    elif setup == "levels2-b2":
        rg = waxman(20, seed=6)
        cp = ControlPlane(rg, levels=2, branching=2, **kw)
    elif setup == "levels2-b3":
        rg, assign = region_tree(2, 3, 3, seed=7)
        cp = ControlPlane(rg, levels=2, branching=3, region_of=assign,
                          fanout=1, **kw)
    else:
        rg, assign = region_tree(3, 2, 3, seed=5)
        cp = ControlPlane(rg, levels=3, branching=2, region_of=assign, **kw)
    cp.register_tenant("a", weight=3.0)
    cp.register_tenant("b", weight=1.0)
    cp.register_tenant("c", weight=2.0, budget=1.5)
    return rg, cp


SETUPS = ("regions3-k1", "regions3-k2", "levels2-b2", "levels2-b3",
          "levels3-b2")


def _handle(h) -> list:
    """A returned handle by kind and id: a Ticket by tid, a spanning
    placement by rid with its chain of (region, reservation) parts."""
    if hasattr(h, "tid"):
        return ["t", int(h.tid), [int(x) for x in h.mapping.route]]
    return ["s", int(h.rid), [[p.region, int(p.tid)] for p in h.parts],
            [list(map(int, c)) for c in h.cuts], list(map(int, h.splits))]


def _state(cp) -> str:
    """Hash of every leaf placer's tickets and float residual arrays."""
    h = hashlib.sha256()
    for _, leaf in cp.leaf_planes():
        pl = leaf.placer
        h.update(np.ascontiguousarray(pl.cap).tobytes())
        h.update(np.ascontiguousarray(pl.bw).tobytes())
        for tid in sorted(pl.tickets):
            tk = pl.tickets[tid]
            h.update(repr((
                int(tid), tk.tenant, int(tk.klass),
                tuple(int(x) for x in tk.mapping.assign),
                tuple(int(x) for x in tk.mapping.route),
                float(tk.mapping.cost),
            )).encode())
    return h.hexdigest()[:20]


def replay(setup: str, seed: int, steps: int = STEPS) -> list[dict]:
    """Drive one plane set-up; return one fingerprint per step."""
    rg, cp = _plane(setup, seed)
    rng = np.random.default_rng(seed)
    links = sorted((u, v) for (u, v) in rg.edges() if u < v)
    down_nodes: list[int] = []
    down_links: list[tuple[int, int]] = []
    out_steps = []
    for step in range(steps):
        op = str(rng.choice(
            ["submit", "hog", "pump", "release", "fail_node",
             "restore_node", "fail_link", "restore_link", "defrag"],
            p=[0.26, 0.06, 0.23, 0.12, 0.07, 0.07, 0.06, 0.06, 0.07],
        ))
        out: list = []
        if op == "submit":
            df = random_dataflow(rg, int(rng.integers(2, 6)),
                                 seed=1000 * seed + step,
                                 creq_range=(0.2, 1.2),
                                 breq_range=(0.5, 6.0))
            out = [cp.submit(str(rng.choice(["a", "b", "c"])), df,
                             klass=int(rng.integers(0, 3)))]
        elif op == "hog":
            # a top-class request between neighbours that takes most of its
            # source's capacity: it preempts whatever lower-class work,
            # spanning segments included, stands there
            u = int(rng.integers(0, rg.n))
            nbrs = sorted(int(x) for x in rg.neighbors(u))
            if nbrs:
                v = nbrs[int(rng.integers(0, len(nbrs)))]
                df = DataflowPath.make([0.95 * float(rg.cap[u]), 0.0], [1.0],
                                       u, v)
                out = [cp.submit("a", df, klass=2)]
        elif op == "pump":
            out = [_handle(h) for h in cp.pump(rounds=int(rng.integers(1, 3)))]
        elif op == "release":
            ids = cp.active_ids()
            if ids:
                rid = int(rng.choice(ids))
                cp.release(rid)
                out = [rid]
        elif op == "fail_node" and len(down_nodes) < 3:
            v = int(rng.integers(0, rg.n))
            if v not in down_nodes:
                alive, requeued = cp.fail_node(v)
                down_nodes.append(v)
                out = [[_handle(h) for h in alive],
                       [_handle(h)[:2] for h in requeued]]
        elif op == "restore_node" and down_nodes:
            cp.restore_node(down_nodes.pop(int(rng.integers(0, len(down_nodes)))))
        elif op == "fail_link" and len(down_links) < 2:
            e = links[int(rng.integers(0, len(links)))]
            if e not in down_links:
                alive, requeued = cp.fail_link(*e)
                down_links.append(e)
                out = [[_handle(h) for h in alive],
                       [_handle(h)[:2] for h in requeued]]
        elif op == "restore_link" and down_links:
            cp.restore_link(*down_links.pop(int(rng.integers(0, len(down_links)))))
        elif op == "defrag":
            out = [[bool(r.committed), bool(r.repacked), int(r.moved)]
                   for r in cp.defrag()]
        cp.check_invariants()
        es = cp.engine_stats()
        out_steps.append({
            "op": op,
            "out": out,
            "ledger": cp.conservation(),
            "span": dict(cp.span_stats),
            "twopc": cp._twopc_msgs,
            "msgs": [es.twopc_messages, es.gossip_messages],
            "state": _state(cp),
        })
    return out_steps


def _digest(rec: dict) -> str:
    return hashlib.sha256(
        json.dumps(rec, sort_keys=True).encode()).hexdigest()[:16]


def record() -> dict:
    """Per case: every step's op and digest, and the last step in full."""
    out = {}
    for setup in SETUPS:
        for seed in SEEDS:
            steps = replay(setup, seed)
            out[f"{setup}/{seed}"] = {
                "steps": [[r["op"], _digest(r)] for r in steps],
                "last": steps[-1],
            }
    return out


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("setup", SETUPS)
def test_golden_replay(setup, seed):
    want = json.loads(GOLDEN.read_text())[f"{setup}/{seed}"]
    got = json.loads(json.dumps(replay(setup, seed)))
    assert len(got) == len(want["steps"])
    for i, (g, (op, digest)) in enumerate(zip(got, want["steps"])):
        assert [g["op"], _digest(g)] == [op, digest], (
            f"{setup}/{seed}: step {i} ({op}) diverged; got {g}")
    assert got[-1] == want["last"]


if __name__ == "__main__":
    GOLDEN.write_text(json.dumps(record(), sort_keys=True) + "\n")
