"""Tensor-parallel sharded serving + the unified ServeConfig surface.

Mesh-dependent cases (divisibility across host-mesh widths, sharded-vs-
single bit-identity) spawn a subprocess with forced host devices so this
file doesn't poison the single-device backend state of the rest of the
suite (the tests/test_sharding.py discipline). Router and ServeConfig
cases run in-process on the normal single-device backend.
"""
import dataclasses
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(src, *argv, timeout=900):
    r = subprocess.run([sys.executable, "-c", src, *argv],
                       capture_output=True, text=True, timeout=timeout,
                       cwd=REPO)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr[-4000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# ShardingPolicy divisibility on 2/4/8-wide serving meshes
# ---------------------------------------------------------------------------

_DIV_WORKER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.configs import assigned_archs, get_config
from repro.launch.mesh import make_serving_mesh
from repro.launch.steps import _params_sds
from repro.sharding import ShardingPolicy

class Leaf:            # shape-only stand-in for a pool array
    def __init__(self, shape): self.shape = shape

def check_specs(specs, tree, sizes, where):
    flat_s, _ = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    flat_x, _ = jax.tree_util.tree_flatten(tree)
    assert len(flat_s) == len(flat_x), where
    for spec, leaf in zip(flat_s, flat_x):
        for dim, entry in zip(leaf.shape, tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            n = 1
            for a in axes:
                n *= sizes[a]
            assert dim % n == 0, (where, leaf.shape, spec)

for width in (2, 4, 8):
    mesh = make_serving_mesh(model=width,
                             devices=jax.devices()[:width])
    sizes = dict(mesh.shape)
    for arch in assigned_archs():
        cfg = get_config(arch)
        policy = ShardingPolicy(cfg, mesh, fsdp=False, parallelism="tp")
        sds = _params_sds(cfg, jnp.bfloat16, quantized=False)
        check_specs(policy.param_specs(sds), sds, sizes,
                    (arch, width, "params"))
        # paged pools shaped like the engine's state cache: plain KV,
        # quantized codes+scale, a cross entry and a recurrent slab
        Hkv, dh = cfg.n_kv_heads, cfg.dh
        caches = {"l0": {"kp": Leaf((2, 8, Hkv, 8, dh)),
                         "vp": Leaf((2, 8, Hkv, 8, dh)),
                         "slab": Leaf((2, 4, dh))},
                  "l1": {"kp": {"codes": Leaf((2, 8, Hkv, 8, dh)),
                                "scale": Leaf((2, 8, Hkv, 128))},
                         "vp": {"codes": Leaf((2, 8, Hkv, 8, dh)),
                                "scale": Leaf((2, 8, Hkv, 128))}},
                  "xk": Leaf((2, 2, Hkv, 16, dh))}
        specs = policy.paged_state_specs(caches)
        check_specs(specs, caches, sizes, (arch, width, "pools"))
        # the head axis shards exactly when the width divides it; slabs
        # and scale head-axes follow the same rule, never unevenly
        want = ("model" if Hkv % width == 0 else None)
        assert tuple(specs["l0"]["kp"])[2] == want, (arch, width)
        assert tuple(specs["l1"]["kp"]["scale"])[2] == want, (arch, width)
        assert tuple(specs["l0"]["slab"]) == (None, None, None), arch
print("OK divisible")
"""


def test_policy_divisible_across_serving_mesh_widths():
    """Every bundled config gets divisible (or replicated) specs for
    params AND paged pools on 2/4/8-wide model meshes — jit inputs
    cannot shard unevenly."""
    assert "OK divisible" in _run(_DIV_WORKER)


# ---------------------------------------------------------------------------
# Sharded-vs-single bit-identity on the pinned greedy workload
# ---------------------------------------------------------------------------

_IDENTITY_WORKER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, "src")
import dataclasses
import jax
import numpy as np
from repro.configs import get_config, reduced
from repro.models import lm as lm_mod
from repro.runtime import Runtime
from repro.serving import ServeConfig, ServeEngine
from repro.serving.engine import Request

kvq = sys.argv[1] == "kvq"
spec = sys.argv[2] == "spec"
fused = sys.argv[3] == "fused"
impl = sys.argv[4] if len(sys.argv) > 4 else "ref"

# the pinned exact-greedy workload (vocab 32 keeps random-init top-2
# logit gaps wide; dh=128 keeps kernels in their deployed regime) with
# n_kv_heads=2 so a 2-wide model axis has a head each
cfg = dataclasses.replace(reduced(get_config("gemma-2b"), vocab=32),
                          head_dim=128, n_kv_heads=2)
params = lm_mod.lm_init(jax.random.PRNGKey(3), cfg)
rt = Runtime(impl=impl, q_chunk=16, kv_quant=kvq,
             kv_scheme="spx_8_x3" if kvq else "none")
rng = np.random.default_rng(3)
prompts = [rng.integers(1, cfg.vocab_size, int(n)).astype(np.int32)
           for n in (3, 9, 17, 6)]

def drive(shards):
    sc = ServeConfig(batch_slots=2, max_seq=64, quantize="sp2_4",
                     kv_layout="paged", page_size=8,
                     spec_decode=spec, spec_k=2 if spec else None,
                     fused_decode=fused, shards=shards)
    eng = ServeEngine(params, cfg, sc, rt=rt)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    out = {r.rid: tuple(r.output) for r in eng.run()}
    return out, eng.metrics()

base, m1 = drive(1)
shrd, m2 = drive(2)
assert base == shrd, (base, shrd)
assert m2["shards"] == 2 and m2["kv_sharded"] is True
assert m2["kv_heads_per_shard"] == 1
# head-sharding halves the per-shard KV bytes
assert m2["peak_kv_bytes_per_shard"] * 2 == m2["peak_kv_bytes"], m2
assert m1["peak_kv_bytes_per_shard"] == m1["peak_kv_bytes"]
print("OK identical", m2["peak_kv_bytes_per_shard"])
"""


@pytest.mark.parametrize("kvq", [False, True], ids=["plain", "spx-kv"])
@pytest.mark.parametrize("spec", [False, True], ids=["nospec", "spec"])
@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_sharded_greedy_bit_identical(kvq, spec, fused):
    """shards=2 on a forced-host mesh reproduces the single-device
    greedy outputs bit-for-bit, with per-shard KV bytes halved."""
    out = _run(_IDENTITY_WORKER, "kvq" if kvq else "plain",
               "spec" if spec else "nospec",
               "fused" if fused else "unfused")
    assert "OK identical" in out


@pytest.mark.parametrize("kvq", [False, True], ids=["plain", "spx-kv"])
def test_sharded_pallas_kernels_bit_identical(kvq):
    """The Pallas kernels (interpret mode) inside the shards=2 program:
    GSPMD cannot partition a Mosaic call, so each runs whole on every
    device of the mesh, and greedy outputs match one device's."""
    out = _run(_IDENTITY_WORKER, "kvq" if kvq else "plain", "nospec",
               "fused", "interpret")
    assert "OK identical" in out


_PLACEMENT_WORKER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, "src")
import dataclasses
import jax
import numpy as np
from repro.configs import get_config, reduced
from repro.core.quantized import QuantizedTensor
from repro.models import lm as lm_mod
from repro.runtime import Runtime
from repro.serving import ReplicaRouter, ServeConfig, ServeEngine
from repro.serving.engine import Request

cfg = dataclasses.replace(reduced(get_config("gemma-2b"), vocab=32),
                          head_dim=128)
params = lm_mod.lm_init(jax.random.PRNGKey(3), cfg)
rt = Runtime(impl="ref", q_chunk=16)
sc = ServeConfig(batch_slots=2, max_seq=64, quantize="sp2_4",
                 kv_layout="paged", page_size=8)
router = ReplicaRouter(params, cfg, sc.replace(replicas=4), rt=rt)
devs = jax.devices()
for i, eng in enumerate(router.engines):
    leaves = jax.tree_util.tree_leaves((eng.params, eng.caches))
    assert all(l.devices() == {devs[i]} for l in leaves), i
rng = np.random.default_rng(3)
prompts = [rng.integers(1, cfg.vocab_size, int(n)).astype(np.int32)
           for n in (3, 9, 17, 6, 11, 4)]
for i, p in enumerate(prompts):
    router.submit(Request(rid=i, prompt=p, max_new_tokens=4))
got = {r.rid: tuple(r.output) for r in router.run()}
solo = ServeEngine(params, cfg, sc, rt=rt)
for i, p in enumerate(prompts):
    solo.submit(Request(rid=i, prompt=p, max_new_tokens=4))
assert got == {r.rid: tuple(r.output) for r in solo.run()}
print("OK placement", router.metrics()["requests_per_replica"])
"""


def test_router_pins_replica_i_to_device_i():
    """Four single-shard replicas on four host devices: replica i's
    weights and state cache live on device i alone, and the fleet's
    greedy outputs match one engine's."""
    assert "OK placement" in _run(_PLACEMENT_WORKER)


# ---------------------------------------------------------------------------
# Replica router (in-process: single device, shards=1)
# ---------------------------------------------------------------------------

import jax  # noqa: E402

from repro.configs import get_config, reduced  # noqa: E402
from repro.models import lm as lm_mod  # noqa: E402
from repro.runtime import Runtime  # noqa: E402
from repro.serving import ReplicaRouter, ServeConfig, ServeEngine  # noqa: E402
from repro.serving.engine import Request  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

RT = Runtime(impl="ref", q_chunk=16)
CFG = dataclasses.replace(reduced(get_config("gemma-2b"), vocab=32),
                          head_dim=128)


@pytest.fixture(scope="module")
def params():
    return lm_mod.lm_init(jax.random.PRNGKey(3), CFG)


def _reqs(n=8, seed=3, new_tokens=4):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(1, CFG.vocab_size,
                                        int(rng.integers(3, 12)))
                    .astype(np.int32),
                    max_new_tokens=new_tokens)
            for i in range(n)]


def test_router_distributes_wave_and_merges_metrics(params):
    """8 identical-load requests over 2 replicas land 4/4 (least-loaded
    with deterministic ties), outputs match a single engine, and the
    fleet metrics sum counters / recompute percentiles."""
    sc = ServeConfig(batch_slots=2, max_seq=64, quantize=None,
                     kv_layout="paged", page_size=8, replicas=2)
    router = ReplicaRouter(params, CFG, sc, rt=RT)
    placements = [router.submit(r) for r in _reqs()]
    assert placements == [0, 1, 0, 1, 0, 1, 0, 1]
    done = router.run()
    assert sorted(r.rid for r in done) == list(range(8))

    solo = ServeEngine(params, CFG,
                       ServeConfig(batch_slots=2, max_seq=64, quantize=None,
                                   kv_layout="paged", page_size=8), rt=RT)
    for r in _reqs():
        solo.submit(r)
    want = {r.rid: tuple(r.output) for r in solo.run()}
    assert {r.rid: tuple(r.output) for r in done} == want

    m = router.metrics()
    assert m["replicas"] == 2 and m["requests_per_replica"] == [4, 4]
    assert m["requests_finished"] == 8
    assert m["tokens_generated"] == sum(len(o) for o in want.values())
    per = m["per_replica"]
    assert len(per) == 2
    assert m["engine_steps"] == sum(p["engine_steps"] for p in per)
    assert m["peak_kv_bytes"] == sum(p["peak_kv_bytes"] for p in per)
    # percentiles recomputed over the union, not averaged
    assert m["ttft_p50_ms"] > 0 and m["latency_p95_ms"] > 0


def test_router_routes_streams_and_rejects_duplicate_rids(params):
    sc = ServeConfig(batch_slots=2, max_seq=64, quantize=None,
                     kv_layout="paged", page_size=8, replicas=2)
    router = ReplicaRouter(params, CFG, sc, rt=RT)
    reqs = _reqs(4)
    for r in reqs:
        router.submit(r)
    with pytest.raises(ValueError, match="already routed"):
        router.submit(Request(rid=0, prompt=reqs[0].prompt,
                              max_new_tokens=2))
    with pytest.raises(KeyError, match="never routed"):
        router.stream(99)
    assert router.cancel(1) is True
    done = router.run()
    assert sorted(r.rid for r in done) == [0, 2, 3]
    assert router.metrics()["requests_cancelled"] == 1


def test_engine_rejects_router_knob(params):
    with pytest.raises(ValueError, match="ReplicaRouter"):
        ServeEngine(params, CFG,
                    ServeConfig(quantize=None, kv_layout="paged",
                                replicas=2), rt=RT)


# ---------------------------------------------------------------------------
# ServeConfig: resolution ownership, validation, one-PR legacy shim
# ---------------------------------------------------------------------------

def test_resolve_fills_every_knob_and_is_idempotent():
    sc = ServeConfig(quantize=None).resolve(CFG)
    assert sc.resolved
    assert sc.kv_layout == "paged"           # auto -> paged
    assert sc.prefill_chunk == 32
    assert sc.scheduler == "cb"
    assert sc.fused_decode is True
    assert sc.spec_decode is False and sc.spec_k == 0
    assert sc.shards == 1 and sc.replicas == 1
    assert sc.resolve(CFG) is sc             # idempotent
    # replace() invalidates; re-resolving the off pair stays off
    again = sc.replace(batch_slots=8).resolve(CFG)
    assert again.spec_k == 0 and not sc.replace(batch_slots=8).resolved


def test_resolve_owns_env_fallbacks(monkeypatch):
    """REPRO_* envs are read in resolve() and nowhere else: an already-
    resolved config is immune to env changes."""
    monkeypatch.setenv("REPRO_SHARDS", "4")
    monkeypatch.setenv("REPRO_REPLICAS", "3")
    monkeypatch.setenv("REPRO_SCHEDULER", "fifo")
    sc = ServeConfig(quantize=None).resolve(CFG)
    assert sc.shards == 4 and sc.replicas == 3 and sc.scheduler == "fifo"
    monkeypatch.setenv("REPRO_SHARDS", "2")
    assert sc.resolve(CFG).shards == 4       # resolved: env not re-read
    # dense degrades the env shards silently; explicit shards= raises
    dense = ServeConfig(quantize=None, kv_layout="dense").resolve(CFG)
    assert dense.shards == 1
    with pytest.raises(ValueError, match="paged"):
        ServeConfig(quantize=None, kv_layout="dense",
                    shards=2).resolve(CFG)


def test_resolve_validates_new_knobs():
    with pytest.raises(ValueError, match="shards"):
        ServeConfig(quantize=None, shards=0).resolve(CFG)
    with pytest.raises(ValueError, match="replicas"):
        ServeConfig(quantize=None, replicas=0).resolve(CFG)


def test_legacy_kwargs_warn_once_and_forward(params):
    """The one-PR shim: old-style knob kwargs still build the same
    engine, under a DeprecationWarning naming ServeConfig."""
    with pytest.warns(DeprecationWarning, match="ServeConfig"):
        eng = ServeEngine(params, CFG, rt=RT, batch_slots=2, max_seq=64,
                          quantize=None, kv_layout="paged", page_size=8)
    assert eng.config.batch_slots == 2
    assert eng.config.page_size == 8 and eng.config.resolved
    with pytest.raises(TypeError, match="ServeConfig"):
        ServeEngine(params, CFG, rt=RT, quantize=None, bogus_knob=1)
    # new-style construction must stay warning-free
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ServeEngine(params, CFG,
                    ServeConfig(batch_slots=2, max_seq=64, quantize=None,
                                kv_layout="paged", page_size=8), rt=RT)
