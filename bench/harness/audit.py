"""The comparison that decides ``correct``.

The window's calls into the plane are recorded in order with what each
left behind (the live placements after every pump and failure, the
requests dropped in every pump).  Once the window has closed, the
reference replays that record on its own ledger and judges each decision:

- every placement the plane made (first placements, re-admissions and
  re-mappings after the failure) keeps the stated rules on the residual it
  was made on (``Reference.invalid``);
- a request's first placement costs what the configuration's reference
  (``first_cost``, ``bench/references``) promises on that residual:
  LeastCostMap's cost for ``leastcost``.  Placements within one pump commit
  in ticket order, so the residual of each is the one before the pump less
  those committed before it.  Re-admissions may commit a cached mapping,
  which the configuration does not hold to least cost, and a pump that
  preempted standing work leaves the order of releases unknown; both are
  checked for validity alone, as is a request the reference promises
  nothing for (``None``);
- a dropped request fits nowhere, by the reference, on the smallest
  residual the pump can have had (everything live before or after it
  still held);
- every request due in the window is decided before the drain ends.

The control puts the reference with its relaxation cut to
``control_supersteps`` supersteps in the program's place: each first
placement's cost is the control's (``first_cost`` with
``max_supersteps``), on the same residual, and is judged as the program's
would be.
"""
from __future__ import annotations

import dataclasses
import math

from .reference import Reference

# A first placement's cost may sit this far from the promised one, relative
# to the cost: float32 round-off is the only slack (the configurations'
# latencies are whole numbers, so a different route differs by >= 1).
COST_TOL = 1e-3


@dataclasses.dataclass
class Placement:
    token: object  # identity of the plane's record of it
    order: int  # commit order within one call
    assign: tuple
    route: tuple
    cost: float


@dataclasses.dataclass
class Readings:
    placements: int = 0  # placements checked for validity
    invalid: int = 0
    checked_cost: int = 0  # first placements compared with the reference
    wrong_costs: int = 0  # cost off the promised one, or fits on one side only
    cost_gap: float = 0.0  # widest |cost - promised| where both fit
    drops: int = 0
    wrong_drops: int = 0
    undecided: int = 0
    first_fault: str = ""

    def fault(self, why: str) -> None:
        if not self.first_fault:
            self.first_fault = why


def replay(net, requests: dict, events: list, *, first_cost, undecided: int,
           control_supersteps: int | None = None) -> Readings:
    """Judge the recorded calls.  ``first_cost`` is the configuration's
    reference (``catalog.reference``); ``requests`` maps rid -> traffic
    ``Request``; ``events`` holds ``("pump", snapshot, drops)``,
    ``("release", rid)``, ``("fail", node, snapshot)`` and
    ``("restore", node)`` in call order, a snapshot mapping rid ->
    ``Placement`` for every live request."""
    ref = Reference(net)
    out = Readings(undecided=undecided)
    live: dict = {}
    placed_once: set = set()

    def take(rid, pl, sign):
        r = requests[rid]
        ref.apply(r.creq, r.breq, pl.assign, pl.route, sign)

    def check(rid, pl):
        r = requests[rid]
        out.placements += 1
        why = ref.invalid(r.creq, r.breq, r.src, r.dst, pl.assign, pl.route,
                          pl.cost)
        if why:
            out.invalid += 1
            out.fault(f"request {rid}: {why}")

    def wrong(cost, best):
        if math.isinf(cost) or math.isinf(best):
            return math.isinf(cost) != math.isinf(best)
        return abs(cost - best) > COST_TOL * max(1.0, best)

    def compare(rid, pl):
        r = requests[rid]
        best = first_cost(ref, r)
        if best is None:
            return
        cost = pl.cost if control_supersteps is None else first_cost(
            ref, r, max_supersteps=control_supersteps)
        out.checked_cost += 1
        if not (math.isinf(best) or math.isinf(cost)):
            out.cost_gap = max(out.cost_gap, abs(cost - best))
        if wrong(cost, best):
            out.wrong_costs += 1
            out.fault(f"request {rid} placed at {cost}; reference {best}")

    for ev in events:
        kind = ev[0]
        if kind == "release":
            take(ev[1], live.pop(ev[1]), +1.0)
        elif kind == "restore":
            ref.up[ev[1]] = True
        elif kind == "fail":
            _, node, snap = ev
            ref.up[node] = False
            moved = [r for r in live if snap.get(r) is None
                     or snap[r].token is not live[r].token]
            for rid in moved:
                take(rid, live.pop(rid), +1.0)
            for rid in sorted((r for r in snap if r not in live),
                              key=lambda r: snap[r].order):
                check(rid, snap[rid])
                take(rid, snap[rid], -1.0)
                live[rid] = snap[rid]
                placed_once.add(rid)
        elif kind == "pump":
            _, snap, drops = ev
            vanished = {r: live.pop(r) for r in list(live)
                        if snap.get(r) is None
                        or snap[r].token is not live[r].token}
            for pl_rid, pl in vanished.items():
                take(pl_rid, pl, +1.0)
            new = sorted((r for r in snap if r not in live),
                         key=lambda r: snap[r].order)
            for rid in new:
                pl = snap[rid]
                check(rid, pl)
                if not vanished and rid not in placed_once:
                    compare(rid, pl)
                take(rid, pl, -1.0)
                live[rid] = pl
                placed_once.add(rid)
            if drops:
                for pl_rid, pl in vanished.items():
                    take(pl_rid, pl, -1.0)
                for rid in drops:
                    r = requests[rid]
                    out.drops += 1
                    best = first_cost(ref, r)
                    if best is not None and not math.isinf(best):
                        out.wrong_drops += 1
                        out.fault(f"request {rid} dropped but fits")
                for pl_rid, pl in vanished.items():
                    take(pl_rid, pl, +1.0)
        else:
            raise ValueError(f"unknown event {kind!r}")
    if out.undecided:
        out.fault(f"{out.undecided} requests never decided")
    return out
