"""The open loop: requests are submitted when due, whatever the plane does.

One thread.  Each pass applies due churn, releases due holds, submits
every request whose due time has passed, pumps the plane once if anything
is queued, and stamps each request that became active or was dropped with
the pump's end.  With nothing queued it sleeps until the next due event.

The window's requests are every arrival due before ``seconds``.  One that
fell due while the last pump was under way is submitted as the window
closes: it was due, and its latency counts from its due time.  The drain
then pumps until each of them is decided.

A request's first decision is its decision: a placement displaced by the
failure and admitted again is not a new request.  Every call that changes
placements is recorded with what it left behind, for the audit.
"""
from __future__ import annotations

import dataclasses
import heapq
import math
import time

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Record:
    requests: dict = dataclasses.field(default_factory=dict)  # rid -> Request
    due: dict = dataclasses.field(default_factory=dict)  # rid -> s (window)
    decided: dict = dataclasses.field(default_factory=dict)  # rid -> (s, kind)
    events: list = dataclasses.field(default_factory=list)
    span_s: dict = dataclasses.field(default_factory=dict)  # harness spans
    pumps: int = 0
    pump_log: list = dataclasses.field(default_factory=list)  # (s, decided)
    submit_lag_s: list = dataclasses.field(default_factory=list)
    # the window closes when the first pass after ``seconds`` begins, so a
    # pump under way at ``seconds`` belongs to it, with its decisions
    window_end: float = 0.0
    drain_end: float = 0.0  # when the drain after the window stopped


class PlaneDriver:
    """The calls the loop makes into a plane, and what it reads back.

    ``reader`` is the configuration's plane reader (``catalog.reader``):
    live placements and drops come through it, so the driver works on any
    plane that has one."""

    def __init__(self, cp, record: Record, reader, clock=time.perf_counter):
        self.cp = cp
        self.rec = record
        self.reader = reader
        self.clock = clock
        self._drops: list = []
        self._last: dict = {}
        # not the list's own append: ``pump`` swaps in a fresh list
        reader.attach(cp, lambda rid: self._drops.append(rid))

    def _span(self, name: str, t0: float) -> None:
        self.rec.span_s[name] = self.rec.span_s.get(name, 0.0) + (
            self.clock() - t0)

    def register(self, tenants) -> None:
        for t in tenants:
            self.cp.register_tenant(t, weight=1.0)

    def submit(self, req, df, due: float) -> int:
        rid = self.cp.submit(req.tenant, df, klass=req.klass)
        self.rec.requests[rid] = req
        self.rec.due[rid] = due
        return rid

    def queued(self) -> int:
        led = self.cp.conservation()
        return led["queued"] + led["in_flight"]

    def pump(self, now) -> bool:
        """Pump once; False when the pump changed nothing (the plane holds
        queued work it will not place until something else changes)."""
        led = self.cp.conservation()
        t0 = self.clock()
        with TraceAnnotation("bench.pump"):
            self.cp.pump(rounds=1)
        self._span("bench.pump", t0)
        t = now()
        self.rec.pumps += 1
        drops, self._drops = self._drops, []
        snap = self.reader.live(self.cp)
        last = self._last
        self._last = snap
        if (not drops and led == self.cp.conservation() and len(snap) ==
                len(last) and all(r in last and last[r].token is p.token
                                  for r, p in snap.items())):
            return False
        self.rec.events.append(("pump", snap, drops))
        decided = self.rec.decided
        n = len(decided)
        for rid in snap:
            if rid not in decided:
                decided[rid] = (t, "admit")
        for rid in drops:
            if rid not in decided:
                decided[rid] = (t, "drop")
        self.rec.pump_log.append((self.clock() - t0, len(decided) - n))
        return True

    def release(self, rid: int) -> bool:
        """Release ``rid`` if it is live; False if it is not (yet)."""
        if not self.reader.is_live(self.cp, rid):
            return False
        t0 = self.clock()
        with TraceAnnotation("bench.release"):
            self.cp.release(rid)
        self._span("bench.release", t0)
        self.rec.events.append(("release", rid))
        return True

    def fail(self, nodes) -> None:
        t0 = self.clock()
        with TraceAnnotation("bench.churn"):
            for v in nodes:
                self.cp.fail_node(int(v))
                self._last = self.reader.live(self.cp)
                self.rec.events.append(("fail", int(v), self._last))
        self._span("bench.churn", t0)

    def restore(self, nodes) -> None:
        t0 = self.clock()
        with TraceAnnotation("bench.churn"):
            for v in nodes:
                self.cp.restore_node(int(v))
                self.rec.events.append(("restore", int(v)))
        self._span("bench.churn", t0)


def preload(drv: PlaneDriver, standing, make_df, *, max_pumps: int = 1000):
    """Submit the standing set and pump until it is all decided."""
    for req in standing:
        drv.submit(req, make_df(req), -math.inf)
    pumps = idle = 0
    patience = getattr(drv.cp, "max_attempts", 8)
    while drv.queued() and pumps < max_pumps and idle < patience:
        idle = 0 if drv.pump(lambda: -math.inf) else idle + 1
        pumps += 1


def run(drv: PlaneDriver, sched, make_df, seconds: float, *,
        drain_s: float = 120.0, on_close=lambda: None,
        clock=time.perf_counter, sleep=time.sleep):
    """The measured window, then the drain: pump until every request due
    in the window is decided, at most ``drain_s`` past the close.
    ``on_close`` is called as the window closes, before the drain.  Returns
    the rids due in the window; ``rec.window_end`` is when it closed and
    ``rec.drain_end`` when the drain stopped."""
    rec = drv.rec
    arrivals = sched.arrivals
    releases = [(req.hold, rid) for rid, req in rec.requests.items()
                if rid in rec.decided and rec.decided[rid][1] == "admit"]
    heapq.heapify(releases)
    due_release: set = set()
    failed = restored = False
    window_rids: list = []
    i = 0
    # pumps in a row that changed nothing: past ``patience`` the plane is
    # holding queued work it will not place until something else changes
    # (every retry spent, or the fair-share drain holding a tenant back),
    # so the loop waits for the next due event instead of spinning
    patience = getattr(drv.cp, "max_attempts", 8)
    idle = 0
    t0 = clock()
    now = lambda: clock() - t0  # noqa: E731
    with TraceAnnotation("bench.window"):
        while True:
            t = now()
            if t >= seconds:
                break
            if not failed and t >= sched.fail_at:
                drv.fail(sched.fail_nodes)
                failed, idle = True, 0
            if failed and not restored and t >= sched.restore_at:
                drv.restore(sched.fail_nodes)
                restored, idle = True, 0
            while releases and releases[0][0] <= t:
                due_release.add(heapq.heappop(releases)[1])
            for rid in list(due_release):
                kind = rec.decided.get(rid, (0, ""))[1]
                if kind == "drop" or drv.release(rid):
                    due_release.discard(rid)
                    idle = 0
            ts = clock()
            with TraceAnnotation("bench.submit"):
                while i < len(arrivals) and arrivals[i].due <= t:
                    req = arrivals[i]
                    rid = drv.submit(req, make_df(req), req.due)
                    rec.submit_lag_s.append(t - req.due)
                    heapq.heappush(releases, (req.due + req.hold, rid))
                    window_rids.append(rid)
                    i += 1
                    idle = 0
            drv._span("bench.submit", ts)
            if idle < patience and drv.queued():
                idle = 0 if drv.pump(now) else idle + 1
                continue
            nxt = min(
                arrivals[i].due if i < len(arrivals) else seconds,
                releases[0][0] if releases else seconds,
                sched.fail_at if not failed else seconds,
                sched.restore_at if failed and not restored else seconds,
                seconds)
            wait = nxt - now()
            if wait > 0:
                ts = clock()
                with TraceAnnotation("bench.sleep"):
                    sleep(wait)
                drv._span("bench.sleep", ts)
    end = rec.window_end = now()
    on_close()
    while i < len(arrivals) and arrivals[i].due < seconds:
        req = arrivals[i]
        window_rids.append(drv.submit(req, make_df(req), req.due))
        rec.submit_lag_s.append(end - req.due)
        i += 1
    idle = 0
    while (any(r not in rec.decided for r in window_rids)
           and now() < end + drain_s and drv.queued() and idle < patience):
        idle = 0 if drv.pump(now) else idle + 1
    rec.drain_end = now()
    return window_rids
