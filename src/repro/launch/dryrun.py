"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production meshes (16x16 single-pod; 2x16x16 multi-pod), print
memory_analysis / cost_analysis, and dump a JSON artifact per cell that the
roofline harness consumes.

The production meshes need 512 devices; ``main()`` forces them via
``launch/hostdev`` *at entry*, not at import — importing this module for
``parse_collectives`` must not poison the importer's device topology.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b \
      --shape train_4k [--multi-pod]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import os

import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import assigned_archs, get_config
from repro.configs.base import LM_SHAPES
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "benchmarks", "artifacts")

_COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\b")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "s4": 0.5, "u4": 0.5, "f8e4m3fn": 1, "f8e5m2": 1}


def _shape_bytes(type_str: str) -> float:
    """'bf16[16,4096,512]{...}' -> bytes."""
    m = re.match(r"([a-z0-9]+)\[([\d,]*)\]", type_str)
    if not m:
        return 0.0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def parse_collectives(hlo_text: str) -> dict:
    """Per-computation collective byte totals + while-loop trip counts.

    Returns {'computations': {name: {'bytes': b, 'ops': n}},
             'whiles': [{'body': name, 'trip_count': t or None}]}
    XLA cost analysis counts While bodies ONCE; the roofline harness
    multiplies each body's collective bytes by its trip count.
    """
    comps: dict = {}
    whiles = []
    cur = None
    consts: dict = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\([^)]*\)\s*->", stripped)
        if m and stripped.endswith("{"):
            cur = m.group(1)
            comps[cur] = {"bytes": 0.0, "ops": 0}
            continue
        if stripped.startswith("}"):
            continue
        if cur is None:
            continue
        cm = re.match(r"%?([\w\.\-]+)\s*=\s*([a-z0-9]+\[[\d,]*\])"
                      r"[^=]*constant\((\d+)\)", stripped)
        if cm:
            consts[(cur, cm.group(1))] = int(cm.group(3))
        wm = re.search(r"=\s*\([^)]*\)\s*while\(|=\s*[a-z0-9]+\[[\d,]*\][^=]*"
                       r"while\(", stripped)
        if wm:
            bm = re.search(r"body=%?([\w\.\-]+)", stripped)
            if bm:
                whiles.append({"body": bm.group(1), "parent": cur,
                               "trip_count": None})
        if _COLLECTIVE_RE.search(stripped):
            if "-done" in stripped.split(" = ")[0]:
                continue  # matching -start already counted
            tm = re.search(r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[\d,]*\]\S*))",
                           stripped)
            if not tm:
                continue
            tstr = tm.group(1)
            if tstr.startswith("("):
                total = sum(_shape_bytes(t.strip())
                            for t in tstr[1:-1].split(",") if "[" in t)
            else:
                total = _shape_bytes(tstr)
            comps[cur]["bytes"] += total
            comps[cur]["ops"] += 1
    # trip counts: find compare-vs-constant in condition computations is
    # brittle; instead the harness passes known trip counts per while body
    # (layer periods, loss chunks, attention chunks) by body name matching.
    return {"computations": comps, "whiles": whiles}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             artifact_dir: str, verbose: bool = True,
             extra_kw: dict | None = None) -> dict:
    cfg = get_config(arch)
    shape = next(s for s in LM_SHAPES if s.name == shape_name)
    skip = None
    for s, why in cfg.shapes():
        if s.name == shape_name:
            skip = why
    if skip:
        return {"arch": arch, "shape": shape_name, "status": skip}

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.monotonic()
    with jax.set_mesh(mesh):
        bundle = build_step(cfg, shape, mesh, **(extra_kw or {}))
        jfn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                      out_shardings=bundle.out_shardings,
                      donate_argnums=bundle.donate_argnums)
        lowered = jfn.lower(*bundle.args)
        t_lower = time.monotonic() - t0
        t0 = time.monotonic()
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)

    n_chips = 512 if multi_pod else 256
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "status": "ok",
        "meta": bundle.meta,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_bytes": (mem.argument_size_in_bytes
                                      + mem.temp_size_in_bytes
                                      + mem.output_size_in_bytes
                                      - mem.alias_size_in_bytes),
        },
        "cost": {"flops": cost.get("flops", 0.0),
                 "bytes": cost.get("bytes accessed", 0.0),
                 "transcendentals": cost.get("transcendentals", 0.0)},
        "collectives": coll,
        "model_flops_dense": 6 * cfg.param_count_estimate()
        * shape.global_batch * shape.seq_len,
        "model_flops_active": 6 * cfg.active_param_count_estimate()
        * shape.global_batch * shape.seq_len,
        "param_count": cfg.param_count_estimate(),
        "active_param_count": cfg.active_param_count_estimate(),
    }
    if verbose:
        peak_gb = result["memory"]["peak_per_device_bytes"] / 1e9
        print(f"[{arch} x {shape_name} x {result['mesh']}] "
              f"compile={t_compile:.0f}s peak/device={peak_gb:.2f}GB "
              f"flops={result['cost']['flops']:.3e} "
              f"coll_ops={sum(c['ops'] for c in coll['computations'].values())}")
        print("  memory_analysis:", mem)
    fname = f"dryrun_{arch.replace('.', '_')}_{shape_name}" \
            f"_{'multi' if multi_pod else 'single'}.json"
    os.makedirs(artifact_dir, exist_ok=True)
    with open(os.path.join(artifact_dir, fname), "w") as f:
        json.dump(result, f, indent=1)
    return result


def main():
    # the production meshes want 512 devices; forcing them here (before
    # the first jax computation initializes the backend) keeps the flag
    # out of importers of this module
    from repro.launch.hostdev import set_host_device_count
    set_host_device_count(512)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--artifact-dir",
                    default=os.path.abspath(ARTIFACT_DIR))
    args = ap.parse_args()

    cells = []
    archs = assigned_archs() if (args.all or not args.arch) else [args.arch]
    for a in archs:
        cfg = get_config(a)
        for s, why in cfg.shapes():
            if args.shape and s.name != args.shape:
                continue
            cells.append((a, s.name))

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = []
    for multi in meshes:
        for a, sname in cells:
            try:
                run_cell(a, sname, multi_pod=multi,
                         artifact_dir=args.artifact_dir)
            except Exception as e:
                traceback.print_exc()
                failures.append((a, sname, multi, str(e)[:200]))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print(f"\nall {len(cells) * len(meshes)} cells compiled OK")


if __name__ == "__main__":
    main()
