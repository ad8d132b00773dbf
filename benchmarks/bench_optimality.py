"""Paper §3.4.1, claim 1: LeastCostMap finds the optimum in ~99% of random
BRITE-style instances, with 100-1000x reduction in partial-map set size.

One row per (topology model, n): optimality rate, mean/max set-size
reduction vs the exact algorithm, fallback + validity rates for the
tensorized JAX DP.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import (
    barabasi_albert, pathmap_exact, random_dataflow, solve, validate_mapping,
    waxman,
)


def run(n_instances: int = 40, sizes=(15, 25), p: int = 6, seed0: int = 0):
    rows = []
    for gen in (waxman, barabasi_albert):
        for n in sizes:
            opt_py = opt_jax = feas = 0
            ratios = []
            fallbacks = 0
            t_py = t_jax = 0.0
            for i in range(n_instances):
                rg = gen(n, seed=seed0 + i)
                df = random_dataflow(rg, p, seed=seed0 + 10_000 + i)
                try:
                    ex, est = pathmap_exact(rg, df, max_states=400_000)
                except MemoryError:
                    continue
                if ex is None:
                    continue
                feas += 1
                t0 = time.perf_counter()
                mp, pst = solve(rg, df, method="leastcost_python")
                t_py += time.perf_counter() - t0
                t0 = time.perf_counter()
                mj, jst = solve(rg, df, method="leastcost_jax")
                t_jax += time.perf_counter() - t0
                if mp is not None and abs(mp.cost - ex.cost) < 1e-4:
                    opt_py += 1
                if mj is not None and abs(mj.cost - ex.cost) < 1e-4:
                    opt_jax += 1
                if mj is not None:
                    ok, _ = validate_mapping(rg, df, mj)
                    assert ok
                fallbacks += jst.fallbacks
                ratios.append(est.max_set_size / max(pst.max_set_size, 1))
            if feas == 0:
                continue
            rows.append({
                "name": f"optimality_{gen.__name__}_n{n}",
                "us_per_call": 1e6 * t_py / max(feas, 1),
                "derived": (
                    f"opt_py={opt_py/feas:.3f};opt_jax={opt_jax/feas:.3f};"
                    f"setsize_reduction_mean={np.mean(ratios):.1f}x;"
                    f"setsize_reduction_max={np.max(ratios):.0f}x;"
                    f"feasible={feas};jax_fallbacks={fallbacks};"
                    f"jax_us={1e6*t_jax/max(feas,1):.0f}"
                ),
            })
    return rows
