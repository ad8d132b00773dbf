"""Knee sweep: the highest Poisson rate a configuration sustains.

    python3 bench/sweep.py --config flat4k --mix steady_flat4k \
        --rates 1.5,2.5,3.5 --seconds 20 --seed 1

One process, one plane: build and warm it as a run does, pre-load the
standing set, then offer the mix open loop at each rate in turn (no
failure burst), draining between rates.  Prints one JSON line per rate:
decisions per second in the window, p50/p90 of due -> decision, and the
requests still undecided at the window's close (the backlog).  The knee is
the highest rate whose backlog stays near zero.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    # libtpu logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from repro.core.device import enable_compile_cache
    from bench.harness import catalog, cell, loop, network, stats, traffic

    if jax.devices()[0].platform != "tpu":
        print("the sweep measures the chip; JAX finds no TPU", file=sys.stderr)
        return 1
    enable_compile_cache(ROOT)
    bench = catalog.benchmark(ROOT)
    config = catalog.config(bench, args.config, ROOT)
    mix = catalog.traffic(args.mix)
    mix.pop("churn", None)
    reader = catalog.reader(config)
    net = network.build(config["network"])
    cp = cell.build_plane(config, net)
    rec = loop.Record()
    drv = loop.PlaneDriver(cp, rec, reader)
    drv.register(mix["tenants"])
    for p in range(mix["p"][0], mix["p"][1] + 1):
        cp.warmup(p=p)
    pre = traffic.build(mix, net, seconds=1.0, seed=args.seed,
                        standing=int(config["standing"]["count"]))
    loop.preload(drv, pre.standing, cell.make_df)
    for i, r in enumerate(float(x) for x in args.rates.split(",")):
        m = dict(mix, arrivals={"process": "poisson", "rate_per_s": r})
        sched = traffic.build(m, net, seconds=args.seconds,
                              seed=args.seed + 1 + i)
        sched = dataclasses.replace(sched, fail_at=math.inf,
                                    restore_at=math.inf)
        t0 = time.perf_counter()
        rids = loop.run(drv, sched, cell.make_df, args.seconds, drain_s=120.0)
        dec = [rec.decided.get(x, (None, ""))[0] for x in rids]
        lat = stats.latencies_ms([rec.due[x] for x in rids], dec,
                                 rec.drain_end)
        inside = sum(1 for t in dec if t is not None and t <= args.seconds)
        print(cell._json({
            "rate_per_s": r, "offered": len(rids),
            "decisions_per_s": inside / args.seconds,
            "p50_ms": stats.percentile(lat, 50) if len(rids) else None,
            "p90_ms": stats.percentile(lat, 90) if len(rids) else None,
            "backlog_at_close": len(rids) - inside,
            "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
