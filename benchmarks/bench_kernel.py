"""Relaxation hot-spot microbenchmark: the bandwidth-masked min-plus move
step, and the batched fused-superstep kernel's tile-size sweep.  On this CPU
container the Pallas kernels run in interpret mode (correctness only — see
tests/test_batched_kernel.py); wall-clock here measures the jnp oracles (the
DP's CPU paths) across problem sizes, and derives the VMEM model for the TPU
kernels from their tile configurations.

``python -m benchmarks.bench_kernel`` writes the batched-kernel sweep
(per-config interpret parity, VMEM-model bytes, fused-ref vs vmapped
timings, chosen defaults) to ``BENCH_kernel.json``.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.minplus import masked_minplus_ref
from repro.kernels.minplus.minplus import BIG, K_TILE, V_TILE, W_TILE


def _inst(n, K, seed=0):
    rng = np.random.default_rng(seed)
    P = np.where(rng.random((n, K)) < 0.3, BIG, rng.random((n, K)) * 10)
    lat = np.where(rng.random((n, n)) < 0.6, BIG, rng.random((n, n)) * 5 + 0.1)
    bw = rng.random((n, n)) * 100
    breq = rng.random(K - 1) * 80
    return (jnp.asarray(P, jnp.float32), jnp.asarray(lat, jnp.float32),
            jnp.asarray(bw, jnp.float32), jnp.asarray(breq, jnp.float32))


# Tile configs swept for the batched superstep kernel, all (8, 128)-aligned
# so each also lowers for the TPU.  Interpret-mode wall clock is an
# emulation (relative) number; the TPU-relevant criterion is the VMEM model:
# pick the largest network tiles that keep the double-buffered live set well
# inside ~16 MB, then the largest b_tile (each increment amortizes one more
# request onto the shared lat/bw tile fetch).
BATCHED_SWEEP = [
    (1, 128, 128),
    (2, 128, 128),
    (4, 128, 128),
    (8, 128, 128),
    (4, 256, 128),
    (4, 128, 256),
]


def run_batched_sweep(*, n: int = 12, ps=(4, 6, 3, 5), seed: int = 9,
                      out_path: str = "BENCH_kernel.json"):
    """Sweep (b_tile, v_tile, w_tile) for the batched superstep:
    interpret-mode parity vs the fused-jnp oracle + per-config VMEM model,
    plus fused-ref vs vmapped-jnp DP timings at online-placer shapes."""
    from repro.core import random_dataflow, waxman
    from repro.core.leastcost import _leastcost_dp_batched
    from repro.core.problem import stack_requests
    from repro.kernels.minplus import batched as bk

    rg = waxman(n, seed=seed)
    dfs = [random_dataflow(rg, p, seed=seed * 100 + i,
                           creq_range=(0.02, 0.2), breq_range=(0.5, 5.0))
           for i, p in enumerate(ps)]
    tensors, p_max = stack_requests(rg, dfs)
    B = len(dfs)
    ref = _leastcost_dp_batched(tensors, B=B, n=n, p=p_max, max_rounds=n - 1,
                                impl="ref")
    sweep = []
    for tiles in BATCHED_SWEEP:
        b_t, v_t, w_t = tiles
        K_pad = -(-(p_max + 1) // bk.K_ALIGN) * bk.K_ALIGN
        t0 = time.perf_counter()
        out = _leastcost_dp_batched(tensors, B=B, n=n, p=p_max,
                                    max_rounds=n - 1, impl="interpret",
                                    tiles=tiles)
        jax.block_until_ready(out[0])
        t_first = time.perf_counter() - t0  # trace/lower/compile dominated
        t0 = time.perf_counter()
        out = _leastcost_dp_batched(tensors, B=B, n=n, p=p_max,
                                    max_rounds=n - 1, impl="interpret",
                                    tiles=tiles)
        jax.block_until_ready(out[0])
        t_warm = time.perf_counter() - t0  # pure emulated execution
        ok = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(ref[:5], out[:5])
        )
        sweep.append({
            "tiles": {"b": b_t, "v": v_t, "w": w_t},
            "parity_vs_ref": ok,
            "first_call_s": t_first,
            "interpret_warm_s": t_warm,
            "vmem_model_bytes": bk.vmem_model_bytes(b_t, v_t, w_t, K_pad),
        })

    # fused-ref vs vmapped-jnp at the shapes the online placer sees
    from repro.core import solve_batch
    timings = []
    for nn, bb in [(16, 8), (24, 32)]:
        rg2 = waxman(nn, seed=3)
        dfs2 = [random_dataflow(rg2, 6, seed=500 + i, creq_range=(0.02, 0.15),
                                breq_range=(0.5, 4.0)) for i in range(bb)]
        solve_batch(rg2, dfs2, method="leastcost_jax")  # warm
        solve_batch(rg2, dfs2, method="leastcost_jax", use_kernel=True)
        t0 = time.perf_counter()
        solve_batch(rg2, dfs2, method="leastcost_jax")
        t_v = time.perf_counter() - t0
        t0 = time.perf_counter()
        solve_batch(rg2, dfs2, method="leastcost_jax", use_kernel=True)
        t_k = time.perf_counter() - t0
        timings.append({"n": nn, "batch": bb, "vmapped_s": t_v,
                        "fused_ref_s": t_k,
                        "speedup": t_v / max(t_k, 1e-9)})

    defaults = dict(zip(("b", "v", "w"), bk.DEFAULT_TILES))
    record = {
        "defaults": defaults,
        "defaults_vmem_bytes": bk.vmem_model_bytes(*bk.DEFAULT_TILES, 8),
        "sweep": sweep,
        "fused_ref_vs_vmapped": timings,
        "note": (
            "first_call_s is trace/lower/compile of the interpret-mode grid "
            "(grows with grid size); interpret_warm_s is pure emulated "
            "execution — neither predicts TPU time; tile choice follows the "
            "VMEM model + largest b_tile"
        ),
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    return record


def run():
    rows = []
    f = jax.jit(masked_minplus_ref)
    for n, K in [(128, 9), (512, 9), (1024, 17), (2048, 17)]:
        args = _inst(n, K)
        jax.block_until_ready(f(*args))  # warmup/compile
        reps = max(3, int(2e8 / (n * n * K)))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = f(*args)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / reps
        work = n * n * K  # min-plus "MACs"
        # TPU kernel VMEM estimate per grid step (v_tile x w_tile x k_tile
        # candidate block + input tiles, fp32)
        vmem = 4 * (V_TILE * W_TILE * K_TILE + V_TILE * K_TILE
                    + 2 * V_TILE * W_TILE + 2 * W_TILE * K_TILE)
        rows.append({
            "name": f"minplus_move_n{n}_K{K}",
            "us_per_call": 1e6 * dt,
            "derived": (
                f"gmacs_per_s={work/dt/1e9:.2f};"
                f"kernel_tiles={V_TILE}x{W_TILE}x{K_TILE};"
                f"kernel_vmem_bytes={vmem}"
            ),
        })
    rec = run_batched_sweep()
    ok = sum(s["parity_vs_ref"] for s in rec["sweep"])
    best = min(rec["fused_ref_vs_vmapped"], key=lambda r: r["fused_ref_s"])
    rows.append({
        "name": "batched_superstep_sweep",
        "us_per_call": 1e6 * best["fused_ref_s"],
        "derived": (
            f"parity={ok}/{len(rec['sweep'])};"
            f"defaults=b{rec['defaults']['b']}v{rec['defaults']['v']}"
            f"w{rec['defaults']['w']};"
            f"vmem_bytes={rec['defaults_vmem_bytes']};"
            f"fused_vs_vmapped={best['speedup']:.2f}x"
        ),
    })
    return rows


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache

    enable_compile_cache()
    print(json.dumps(run_batched_sweep(), indent=2))
