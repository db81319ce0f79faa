"""Compile a cell's weight maker and engine steps for a described TPU
v5e, on the CPU, and print each program's memory analysis against the
cell's memory reckoning. Nothing runs; no chip is needed.

  JAX_PLATFORMS=cpu python3 bench/compile_check.py --workload <cell>
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.models import lm as lm_mod
    from repro.nn.layers import quantize_params
    from repro.runtime import Runtime, planner
    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.find_cell(harness.load_spec(), args.workload)
    c = cell.config
    arch = harness.arch_config(c)
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev),
            tree)

    gib = 2 ** 30
    make = harness.model_module(c).program_params_fn(
        c, lambda t: quantize_params(t, c["weights"]))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=dev)
    params = sds(jax.eval_shape(make, key))
    m = make.lower(key).compile().memory_analysis()
    print(f"weight maker: output {m.output_size_in_bytes / gib:.3f} GiB, "
          f"temp {m.temp_size_in_bytes / gib:.3f} GiB", flush=True)
    kv = c["kv"]
    quant = kv != "f32"
    b, max_seq = c["batch_slots"], c["max_seq"]
    ps = planner.plan_kv_pages(arch.n_kv_heads, arch.dh,
                               rep=arch.n_heads // arch.n_kv_heads,
                               act_bytes=4,
                               kv_scheme=kv if quant else None).page_size
    pps = -(-max_seq // ps)
    caches = sds(jax.eval_shape(lambda: lm_mod.paged_init_caches(
        arch, b * pps, ps, dtype=jnp.float32, kv_quant=quant)))
    pool = sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(caches))
    print(f"KV pool {pool / gib:.3f} GiB ({b} slots x {max_seq} tokens, "
          f"page {ps})", flush=True)
    rt = Runtime(impl="pallas", q_chunk=256, kv_quant=quant,
                 kv_scheme=kv if quant else "uniform8")
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=dev)
    for name, fn, width in (("prefill step", lm_mod.lm_paged_step, 32),
                            ("fused decode step",
                             lm_mod.lm_paged_fused_step, 1)):
        step = jax.jit(fn, static_argnums=(7, 8), donate_argnums=(6,))
        m = step.lower(params, i32(b, width), i32(b), i32(b, pps), i32(b),
                       i32(b, 2), caches, arch, rt).compile()
        m = m.memory_analysis()
        total = (m.argument_size_in_bytes + m.temp_size_in_bytes
                 + m.output_size_in_bytes - m.alias_size_in_bytes)
        print(f"{name} (W={width}): arguments "
              f"{m.argument_size_in_bytes / gib:.3f} GiB, temp "
              f"{m.temp_size_in_bytes / gib:.3f} GiB, output "
              f"{m.output_size_in_bytes / gib:.3f} GiB, aliased "
              f"{m.alias_size_in_bytes / gib:.3f} GiB: about "
              f"{total / gib:.3f} GiB of the chip's 15.75", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
