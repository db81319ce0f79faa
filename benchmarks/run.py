"""Benchmark harness: one function per paper table/figure plus the kernel
microbenchmark and the dense-vs-paged serving comparison (which writes
``BENCH_serving.json`` at the repo root). Prints
``name,us_per_call,derived`` CSV at the end.

  PYTHONPATH=src python -m benchmarks.run [--skip-roofline-table]
"""
import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def kernel_microbench(csv_rows):
    """spx_matmul: ref vs interpret-mode Pallas (correct-by-construction
    check is in tests; here: bytes-moved accounting, the paper's actual
    win on TPU)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.quantized import quantize_weight
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    m, k, n = 256, 1024, 1024
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)) * 0.03, jnp.float32)
    print("\n== spx_matmul storage/traffic accounting ==")
    dense_bytes = w.size * 2                      # bf16 weights
    for scheme in ("sp2_8", "sp2_4"):
        qt = quantize_weight(w, scheme)
        qbytes = qt.nbytes_stored()
        f = jax.jit(lambda xx, q: ops.spx_matmul(xx, q, impl="ref"))
        jax.block_until_ready(f(x, qt))
        t0 = time.monotonic()
        for _ in range(10):
            jax.block_until_ready(f(x, qt))
        t = (time.monotonic() - t0) / 10
        print(f"  {scheme:6s}: weight bytes {qbytes/1e3:8.1f}KB "
              f"({dense_bytes/qbytes:.1f}x smaller than bf16), "
              f"{t*1e6:8.0f} us/call (host ref path)")
        csv_rows.append((f"kernel/spx_matmul_{scheme}", t * 1e6,
                         dense_bytes / qbytes))


def plan_report(csv_rows):
    """Execution plans the runtime picks for each bundled config's hot
    matmul (d_model -> d_ff at serving batch 256): block geometry from the
    §3.1 analytical model, with the pipeline margin the paper argues in
    prose. These are the tiles the Pallas kernels actually run with."""
    from repro.configs import get_config, list_configs
    from repro.core.pipeline import current_hw
    from repro.runtime import planner

    print("\n== execution plans (spx_matmul, 4-bit, m=256) ==")
    print(f"  {'arch':22s} {'K->N':>14s}  bm x bn x bk   margin  vmem(MB)")
    for name in list_configs():
        cfg = get_config(name)
        k_dim, n_dim = cfg.d_model, cfg.d_ff or cfg.d_model
        plan = planner.plan_matmul(256, k_dim, n_dim, weight_bits=4,
                                   packed=True)
        if plan is None:
            print(f"  {name:22s} {k_dim:6d}->{n_dim:<6d}  (ref fallback: "
                  "ragged dims)")
            continue
        print(f"  {name:22s} {k_dim:6d}->{n_dim:<6d}  "
              f"{plan.bm:4d}x{plan.bn:4d}x{plan.bk:4d} "
              f"{plan.margin:7.2f}  {plan.vmem_bytes/2**20:7.2f}")
        assert plan.vmem_bytes <= current_hw().vmem_bytes
        csv_rows.append((f"plan/{name}", 0.0, plan.margin))


def roofline_table(csv_rows):
    """Summarize any roofline artifacts present (produced by
    `python -m benchmarks.roofline --all`)."""
    art = os.path.join(os.path.dirname(__file__), "artifacts")
    files = sorted(glob.glob(os.path.join(art, "roofline_*.json")))
    if not files:
        print("\n(no roofline artifacts yet — run benchmarks.roofline)")
        return
    print("\n== roofline summary (see EXPERIMENTS.md §Roofline) ==")
    for f in files:
        with open(f) as fh:
            r = json.load(fh)
        if r.get("status") != "ok":
            continue
        csv_rows.append((f"roofline/{r['arch']}/{r['shape']}"
                         + ("_dense" if not r.get("quantized_serving", True)
                            else ""),
                         r["bound_s"] * 1e6, r["roofline_fraction"]))
        print(f"  {r['arch']:22s} {r['shape']:12s} "
              f"{'q' if r.get('quantized_serving', True) else 'd'} "
              f"dom={r['dominant']:10s} bound={r['bound_s']*1e3:9.2f}ms "
              f"frac={r['roofline_fraction']:.2f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-roofline-table", action="store_true")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    csv_rows: list = []
    from benchmarks import fig5, quant_quality, serving_bench, table1
    table1.run(csv_rows)
    quant_quality.run(csv_rows)
    fig5.run(csv_rows)
    kernel_microbench(csv_rows)
    plan_report(csv_rows)
    serving_bench.run(csv_rows)
    if not args.skip_roofline_table:
        roofline_table(csv_rows)

    print("\nname,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.4f},{derived:.4f}")


if __name__ == '__main__':
    main()
