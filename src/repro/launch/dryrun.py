import os
_SCALE = int(os.environ.get("REPRO_DRYRUN_SCALE", "16"))  # mesh edge (tests: 4)
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=" + str(2 * _SCALE * _SCALE)
)
# ^ MUST precede every other import (jax locks the device count on first
#   init).  This module is the ONLY place the 512-device world is created;
#   tests/benches see the real single CPU device.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the production mesh (16x16 single-pod or 2x16x16
multi-pod), constructs the jit'd step (train_step / prefill / serve_step)
with full production shardings, then::

    lowered  = step.lower(*abstract_inputs)      # ShapeDtypeStructs only
    compiled = lowered.compile()
    compiled.memory_analysis()                   # proves it fits HBM
    compiled.cost_analysis()                     # FLOPs / bytes for roofline

and extracts the collective-traffic profile (all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute operand bytes) from the
optimized HLO — cost_analysis does not report collectives (EXPERIMENTS.md
§Dry-run / §Roofline read these JSONs).

Usage::

    python -m repro.launch.dryrun --arch qwen2.5-14b --shape train_4k \
        --mesh single --out results/dryrun
"""
import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import LONG_CONTEXT_OK, get_config, train_accumulation, train_mode
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step
from repro.models.config import SHAPES

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_profile(hlo_text: str) -> dict:
    """Sum operand bytes per collective kind from optimized HLO text."""
    prof = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    biggest: list = []
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)(\()", line)
        if not m:
            continue
        op = m.group(2)
        kind = None
        for k in _COLLECTIVES:
            if op == k or op.startswith(k + "-"):  # e.g. all-reduce-start
                kind = k
                break
        if kind is None or op.endswith("-done"):
            continue
        # operand types: inside the call parens
        call = line[line.index(m.group(3)) :]
        operands = _shape_bytes(call)
        if operands == 0:  # fall back to result type
            operands = _shape_bytes(m.group(1))
        prof[kind]["count"] += 1
        prof[kind]["bytes"] += operands
        biggest.append((operands, kind, line[:160]))
    biggest.sort(reverse=True)
    prof["top_ops"] = [
        {"bytes": b, "kind": k, "hlo": h} for b, k, h in biggest[:12]
    ]
    return prof


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             *, save_hlo: bool = False) -> dict:
    t0 = time.time()
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and arch not in LONG_CONTEXT_OK:
        raise SystemExit(f"{arch} x long_500k is a documented skip (DESIGN.md §6)")
    if _SCALE == 16:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    else:  # test scale: same topology, smaller edge
        from repro.launch.mesh import _mk
        if mesh_kind == "multi":
            mesh = _mk((2, _SCALE, _SCALE), ("pod", "data", "model"))
        else:
            mesh = _mk((_SCALE, _SCALE), ("data", "model"))
    kw = {}
    if shape.kind == "train":
        kw["n_acc"] = train_accumulation(arch)
        kw["mode"] = train_mode(arch)
    with mesh:
        built = build_step(cfg, shape, mesh, **kw)
        lowered = built.fn.lower(*built.abstract_args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()

    prof = collective_profile(hlo)
    loop_aware = hlo_cost.analyze(hlo)
    n_chips = mesh.devices.size
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "chips": int(n_chips),
        "kind": shape.kind,
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "n_acc": kw.get("n_acc", 1),
        "mode": kw.get("mode", "tp"),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "timing": {"lower_s": t_lower, "compile_s": t_compile},
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "cost": {
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
            "transcendentals": cost.get("transcendentals"),
        },
        # loop-aware per-device profile (launch/hlo_cost.py): the roofline
        # source of truth — XLA cost_analysis counts while bodies once.
        "loop_aware": loop_aware,
        "collectives": prof,
    }
    os.makedirs(out_dir, exist_ok=True)
    stem = f"{arch.replace('/', '_')}__{shape_name}__{mesh_kind}"
    with open(os.path.join(out_dir, stem + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    if save_hlo:
        with open(os.path.join(out_dir, stem + ".hlo.txt"), "w") as f:
            f.write(hlo)
    print(f"[dryrun] {stem}: compile={t_compile:.1f}s "
          f"flops={result['cost']['flops']:.3e} "
          f"mem(arg={result['memory']['argument_bytes']}, "
          f"temp={result['memory']['temp_bytes']})")
    print("memory_analysis:", mem)
    print("cost_analysis keys:", {k: cost[k] for k in sorted(cost) if isinstance(cost[k], (int, float))})
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()
    run_cell(args.arch, args.shape, args.mesh, args.out, save_hlo=args.save_hlo)


if __name__ == "__main__":
    main()
