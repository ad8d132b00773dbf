"""Placement-plane benchmark: one run of one cell on the chips it names.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's network and plane (``BENCHMARK.json`` -> configuration,
traffic mix), warms the shapes the mix uses, pre-loads the standing set,
offers the mix open loop for ``--seconds``, drains, checks every decision
against the plain reference, and prints one JSON object as the last line
of standard output.  ``--trace 1`` profiles the window and reports the
per-layer metrics instead of the end-to-end ones.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 1.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    # libtpu logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from repro.core.device import enable_compile_cache
    except ImportError as e:
        print(f"the program is not in this checkout: {e}", file=sys.stderr)
        return 2
    import jax

    from bench.harness import catalog, cell

    bench = catalog.benchmark(ROOT)
    want = catalog.cell(bench, args.workload)["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"needs {want} TPU chip(s); JAX finds {len(devices)} "
              f"{devices[0].platform!r} device(s)", file=sys.stderr)
        return 1
    enable_compile_cache(ROOT)
    result = cell.run(args.workload, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=T_START, bench=bench)
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name} {value} limit {limit}", file=sys.stderr)
    print(cell._json(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
