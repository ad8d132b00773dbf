"""How the harness reads the centralized ``ControlPlane``: live placements
come from its ``active`` table, and its ``on_drop`` hook is called with the
dropped ``Request``."""
from bench.harness.audit import Placement


def attach(cp, on_drop) -> None:
    """Install ``on_drop(rid)`` as the plane's drop hook."""
    if not hasattr(cp, "active"):
        raise NotImplementedError(
            "the centralized reader reads placements from "
            f"ControlPlane.active; {type(cp).__name__} has none")
    cp.on_drop = lambda req: on_drop(req.rid)


def live(cp) -> dict:
    """rid -> ``Placement`` of every live request; the token is the ticket,
    which the plane replaces whenever it places the request anew."""
    return {rid: Placement(t, t.tid, tuple(t.mapping.assign),
                           tuple(t.mapping.route), float(t.mapping.cost))
            for rid, (_, t) in cp.active.items()}


def is_live(cp, rid: int) -> bool:
    return rid in cp.active


def committed_share(cp) -> float:
    return cp.placer.utilization()["nodes_committed"]
