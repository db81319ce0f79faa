"""Distribution machinery on a small host-device mesh (the 512-device
production dry-run is launch/dryrun.py; these tests validate the same
code paths in CI scale)."""
import os
import subprocess
import sys

import pytest

# spawn a subprocess with 8 host devices so this file doesn't poison the
# single-device state of the rest of the suite
_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax
import jax.numpy as jnp

import sys
sys.path.insert(0, "src")
from repro.configs import get_config, reduced
from jax.sharding import AxisType
from repro.configs.base import ShapeConfig
from repro.launch.steps import build_step
from repro.sharding import make_policy

def small_mesh():
    return jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

def run_cell(arch, kind):
    cfg = reduced(get_config(arch), d_model=64, vocab=512)
    # dims divisible by the 4-wide model axis
    cfg = dataclasses.replace(cfg, n_kv_heads=cfg.n_kv_heads)
    shape = ShapeConfig("t", seq_len=64, global_batch=8, kind=kind)
    mesh = small_mesh()
    with jax.set_mesh(mesh):
        bundle = build_step(cfg, shape, mesh)
        jfn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                      out_shardings=bundle.out_shardings,
                      donate_argnums=bundle.donate_argnums)
        compiled = jfn.lower(*bundle.args).compile()
    assert compiled.cost_analysis().get("flops", 0) > 0
    print(f"OK {arch} {kind}")

arch, kind = sys.argv[1], sys.argv[2]
run_cell(arch, kind)
"""


def _run(arch: str, kind: str):
    r = subprocess.run(
        [sys.executable, "-c", _WORKER, arch, kind],
        capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, f"{arch}/{kind}:\n{r.stdout}\n{r.stderr[-3000:]}"
    assert f"OK {arch} {kind}" in r.stdout


@pytest.mark.parametrize("arch", ["granite-3-8b", "olmoe-1b-7b",
                                  "jamba-1.5-large-398b", "xlstm-350m",
                                  "whisper-small", "qwen2-vl-2b"])
def test_train_step_compiles_sharded(arch):
    _run(arch, "train")


@pytest.mark.parametrize("arch", ["granite-3-8b", "olmoe-1b-7b",
                                  "xlstm-350m"])
def test_decode_step_compiles_sharded(arch):
    _run(arch, "decode")


@pytest.mark.parametrize("arch", ["granite-3-8b", "whisper-small"])
def test_prefill_step_compiles_sharded(arch):
    _run(arch, "prefill")


def test_policy_specs_divisible():
    """Every input sharding the policy assigns must divide the dim size
    (jit inputs cannot shard unevenly)."""
    wrk = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.configs import get_config
from jax.sharding import AxisType
from repro.launch.steps import _params_sds
from repro.sharding import make_policy

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
sizes = dict(mesh.shape)
for arch in ("granite-3-8b", "kimi-k2-1t-a32b", "jamba-1.5-large-398b"):
    cfg = get_config(arch)
    sds = _params_sds(cfg, jnp.bfloat16, quantized=False)
    policy = make_policy(cfg, mesh)
    specs = policy.param_specs(sds)
    flat_s, _ = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    flat_x, _ = jax.tree_util.tree_flatten(sds)
    for spec, leaf in zip(flat_s, flat_x):
        for dim, entry in zip(leaf.shape, tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            n = 1
            for a in axes:
                n *= sizes[a]
            assert dim % n == 0, (arch, leaf.shape, spec)
print("OK divisible")
"""
    r = subprocess.run([sys.executable, "-c", wrk], capture_output=True,
                       text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK divisible" in r.stdout
