"""Benchmark harness: one module per paper table/claim.  Prints
``name,us_per_call,derived`` CSV (EXPERIMENTS.md cites these numbers),
then aggregates every ``BENCH_*.json`` artifact the suites wrote into
``BENCH_summary.json`` — a flat metric map plus a bounded trajectory of
previous summaries — and prints a one-screen delta table against the
previous record.

    PYTHONPATH=src python -m benchmarks.run [--quick]
    PYTHONPATH=src python -m benchmarks.run --summarize   # aggregate only
"""
import argparse
import glob
import json
import math
import os
import sys
import time

# non-record artifacts: the summary itself, and the Perfetto event dump
_SKIP = {"BENCH_summary.json", "BENCH_trace_events.json"}
_ENTRY_KEYS = ("generated_at", "sources", "criteria_pass",
               "criteria_failed", "metrics")


def _flatten(obj, prefix="", out=None, depth=0):
    """Dotted-path flattening of the scalar/bool leaves.  Short lists are
    indexed by their row label (``plane`` / ``name`` / ``pipeline_depth``)
    when they have one, so trajectory keys stay stable as rows reorder."""
    if out is None:
        out = {}
    if depth > 7:
        return out
    if isinstance(obj, dict):
        for k, v in obj.items():
            _flatten(v, f"{prefix}.{k}" if prefix else str(k), out,
                     depth + 1)
    elif isinstance(obj, bool):
        out[prefix] = obj
    elif isinstance(obj, (int, float)):
        if math.isfinite(obj):
            out[prefix] = obj
    elif isinstance(obj, list) and len(obj) <= 16:
        for i, v in enumerate(obj):
            label = i
            if isinstance(v, dict):
                label = v.get("plane") or v.get("name") \
                    or v.get("pipeline_depth") or i
            _flatten(v, f"{prefix}[{label}]", out, depth + 1)
    return out


def _print_delta(old_metrics, metrics, criteria, sources,
                 max_rows: int = 24) -> None:
    failed = sorted(k for k, v in criteria.items() if not v)
    print(f"\n== BENCH_summary: {len(metrics)} metrics "
          f"from {len(sources)} artifacts; "
          f"criteria {len(criteria) - len(failed)}/{len(criteria)} pass")
    for k in failed:
        print(f"   FAIL {k}")
    if not old_metrics:
        print("   (no previous summary — baseline recorded)")
        return
    rows = []
    for k, v in metrics.items():
        o = old_metrics.get(k)
        if isinstance(v, bool) or not isinstance(o, (int, float)) \
                or isinstance(o, bool) or o == v:
            continue
        rel = abs(v - o) / max(abs(o), 1e-12)
        rows.append((rel, k, o, v))
    if not rows:
        print("   (no numeric metric changed since the previous summary)")
        return
    rows.sort(reverse=True)
    print(f"   top deltas vs previous ({min(len(rows), max_rows)} "
          f"of {len(rows)} changed):")
    for rel, k, o, v in rows[:max_rows]:
        sign = "+" if v >= o else "-"
        print(f"   {k:64.64s} {o:>12.4g} -> {v:>12.4g}  "
              f"({sign}{100 * rel:.1f}%)")


def summarize(out_path: str = "BENCH_summary.json", directory: str = ".",
              trajectory_cap: int = 20, quiet: bool = False) -> dict:
    """Fold every ``BENCH_*.json`` in ``directory`` into one summary
    record.  The previous summary (if any) is pushed onto a bounded
    ``trajectory`` list, so the artifact carries its own history across
    CI runs; the delta table prints current vs previous."""
    files = sorted(
        f for f in glob.glob(os.path.join(directory, "BENCH_*.json"))
        if os.path.basename(f) not in _SKIP
    )
    metrics, criteria, sources = {}, {}, []
    for path in files:
        tag = os.path.basename(path)[len("BENCH_"):-len(".json")]
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            print(f"summarize: skipping {path}: {e}", file=sys.stderr)
            continue
        sources.append(os.path.basename(path))
        for k, v in _flatten(doc, tag).items():
            metrics[k] = v
            # every criterion gate and module-level ok flag, pass or fail
            if ".criterion." in k or k.endswith(".ok"):
                criteria[k] = bool(v)
    entry = {
        "generated_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "sources": sources,
        "criteria_pass": all(criteria.values()) if criteria else None,
        "criteria_failed": sorted(k for k, v in criteria.items() if not v),
        "metrics": metrics,
    }
    prev = None
    if os.path.exists(out_path):
        try:
            with open(out_path) as fh:
                prev = json.load(fh)
        except (OSError, json.JSONDecodeError):
            prev = None
    trajectory = []
    if prev:
        trajectory = list(prev.get("trajectory", []))
        trajectory.append({k: prev[k] for k in _ENTRY_KEYS if k in prev})
        trajectory = trajectory[-trajectory_cap:]
    summary = dict(entry, trajectory=trajectory)
    with open(out_path, "w") as fh:
        json.dump(summary, fh, indent=2, sort_keys=True)
        fh.write("\n")
    if not quiet:
        _print_delta(prev.get("metrics") if prev else None, metrics,
                     criteria, sources)
    return summary


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer instances")
    ap.add_argument("--only", default="", help="substring filter")
    ap.add_argument("--summarize", action="store_true",
                    help="skip the suites; aggregate existing BENCH_*.json "
                         "into BENCH_summary.json and print the delta table")
    args = ap.parse_args()

    if not args.summarize:
        from benchmarks import (
            bench_kernel, bench_messages, bench_optimality, bench_placement,
            bench_scaling, bench_trace,
        )

        suites = [
            ("optimality", lambda: bench_optimality.run(
                n_instances=10 if args.quick else 40)),
            ("messages", lambda: bench_messages.run(
                n_instances=8 if args.quick else 25)),
            ("scaling", lambda: bench_scaling.run(smoke=args.quick)),
            ("kernel", bench_kernel.run),
            ("placement", bench_placement.run),
            ("trace", lambda: bench_trace.run(smoke=True)),
        ]
        print("name,us_per_call,derived")
        for name, fn in suites:
            if args.only and args.only not in name:
                continue
            try:
                for row in fn():
                    print(f"{row['name']},{row['us_per_call']:.1f},"
                          f"\"{row['derived']}\"")
            except Exception as e:  # keep the harness running
                print(f"{name}_FAILED,0,\"{type(e).__name__}: {e}\"",
                      file=sys.stdout)
    summarize()
    sys.stdout.flush()


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache

    enable_compile_cache()

    main()
