"""Readings of the comparison for the program and for its control.

    python3 bench/control.py --workload flat4k-steady --seeds 1,2,3 --seconds 51

Runs the cell once per seed in one process, as ``bench/run.py`` does, and
checks each window twice with the same check: once as the program
answered it, once with the control in the program's place (the
reference, its relaxation cut to ``cell.CONTROL_SUPERSTEPS`` supersteps).
Prints one line per seed and side with ``correct`` and the numbers
compared.  The limits in ``cell.limits`` are set from these readings; the
benchmark's own runs do not run the control.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import io
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    # libtpu logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from repro.core.device import enable_compile_cache
    from bench.harness import cell

    if jax.devices()[0].platform != "tpu":
        print("the readings are taken on the chip; JAX finds no TPU",
              file=sys.stderr)
        return 1
    enable_compile_cache(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        m = cell.measure(args.workload, seed=seed, seconds=args.seconds,
                         trace=False, t_start=time.perf_counter())
        for side, control in (("program", None),
                              ("control", cell.CONTROL_SUPERSTEPS)):
            log = io.StringIO()
            r = cell.report(m, control=control, log=log)
            readings = log.getvalue().strip().splitlines()[-1]
            print(cell._json({"seed": seed, "side": side,
                              "correct": r["correct"],
                              "metrics": r["metrics"],
                              "compared": r["compared"]}), flush=True)
            print(readings, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
