"""Decentralized regional control plane, end to end.

A 6-region *line* topology (fully-connected 4-node regions, one gateway
link between neighbors) is sharded with ``ControlPlane(rg, regions=6,
region_of=...)``.  Each region drains its own tenant queues against its
own **compacted** residual view — every regional DP solve runs over
n_r = 4 nodes, never the global 24 (the view substrate of
``repro.core.compact``) — and fair shares are enforced from *gossiped
estimates* of what every tenant holds elsewhere (no global lock,
R * fanout messages per round).  The same broker nests: with
``ControlPlane(rg, levels=L, branching=b, region_of=...)`` each region is
itself a broker of regions, down to the region-local leaf planes.

A dataflow pinned from region 0 to region 2 has no direct cut edge: it is
decomposed over the multi-hop region chain 0 -> 1 -> 2 (one gateway-pinned
segment per region, region 1 possibly pure transit) and placed by ONE
bounded two-phase commit.  A middle cut-link failure partitions the
chain — the spanning placement is displaced, queued, and re-admitted
after the heal.

Run:  PYTHONPATH=src python examples/regional_service.py
"""
import numpy as np

from repro.core import DataflowPath, region_line
from repro.service import ControlPlane, FairSharePolicy, SpanningTicket


def main():
    rg, assign = region_line(6, 4, seed=11)
    cp = ControlPlane(rg, regions=6, region_of=assign, fanout=2, seed=0,
                      policy=FairSharePolicy(slack=0.4), micro_batch=16)
    print(f"{cp.R} regions in a line over {rg.n} nodes, "
          f"{len(cp.cut_base)} cut links "
          f"(region sizes {np.bincount(cp.region_of).tolist()}, "
          f"every solve compacted to n_r = "
          f"{max(v.n_local for v in cp.views)})")

    cp.register_tenant("gold", weight=3.0)
    cp.register_tenant("bronze", weight=1.0)
    cp.warmup(p=4)  # pre-compile every region's jit buckets before pumping

    # Overload both tenants with mixed-span work: in-region requests and
    # requests straddling 2..4 regions along the line.
    rng = np.random.default_rng(7)
    for i in range(60):
        for tenant in ("gold", "bronze"):
            r1 = int(rng.integers(0, 6))
            r2 = min(5, r1 + int(rng.integers(0, 4)))
            src = int(rng.choice(np.nonzero(assign == r1)[0]))
            dst = int(rng.choice(np.nonzero(assign == r2)[0]))
            if src == dst:
                continue
            p = int(rng.integers(2, 5))
            creq = rng.uniform(0.05, 0.3, p).astype(np.float32)
            creq[0] = creq[-1] = 0.0
            breq = rng.uniform(0.5, 2.0, p - 1).astype(np.float32)
            cp.submit(tenant, DataflowPath(creq, breq, src, dst))
    for _ in range(8):
        cp.pump()
    cp.check_invariants()

    held = cp.committed_capacity()
    rep = cp.fairness_report()
    coord = cp.coordination_report()
    size = coord["solve_size"]
    print(f"standing capacity  gold={held['gold']:.2f} "
          f"bronze={held['bronze']:.2f} "
          f"(weighted max-min deviation {rep['max_deviation']:.1%})")
    print(f"coordination: {coord['gossip_messages']} gossip msgs "
          f"({coord['gossip_messages_per_round']:.0f}/round = R*fanout), "
          f"{coord['twopc_messages']} 2PC msgs for "
          f"{coord['spanning']['admitted']} spanning placements "
          f"(longest chain {coord['spanning']['max_chain']} regions, "
          f"{coord['spanning']['multi_hop']} multi-hop)")
    print(f"solve size: mean padded n per regional solve = "
          f"{size['mean_solve_n']:.1f} (global n = {size['global_n']}; "
          f"{size['global_n'] / size['mean_solve_n']:.0f}x smaller DP)")

    # A dataflow pinned across THREE regions (0 -> 2): no direct cut edge
    # exists, so it is decomposed over the region chain by multi-hop 2PC.
    src = int(np.nonzero(assign == 0)[0][0])
    dst = int(np.nonzero(assign == 2)[0][-1])
    df = DataflowPath.make([0.0, 0.2, 0.2, 0.0], [1.0, 1.0, 1.0], src, dst)
    rid = cp.submit("gold", df)
    spans = [t for t in cp.pump()
             if isinstance(t, SpanningTicket) and t.rid == rid]
    if spans:
        st = spans[-1]
        print(f"spanning rid {rid}: chain {st.chain} "
              f"(splits {st.splits}), cuts {st.cuts}, "
              f"{[f'{b:.1f}' for b in st.cut_bws]} bw reserved by one 2PC")

        # Partition the chain at its middle cut: the whole composite
        # placement is displaced (never dropped), then heals + re-admits.
        mid = st.cuts[len(st.cuts) // 2]
        cp.fail_link(*mid)
        led = cp.conservation()
        print(f"middle cut {mid} failed: active={led['active']} "
              f"queued={led['queued']} dropped={led['dropped']}")
        cp.restore_link(*mid)
        cp.pump()
        print(f"healed: rid {rid} active again = "
              f"{rid in cp.active_ids()}")

    # Per-region background defrag — no global re-solve exists, by design.
    results = cp.defrag()
    print("regional defrag:",
          [(r.committed, r.moved, len(r.readmitted)) for r in results])

    cp.check_invariants()
    print("ledger:", cp.conservation())


if __name__ == "__main__":
    main()
