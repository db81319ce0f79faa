"""CPU tests of the benchmark harness: trace reduction, work functions,
traffic, discovery by name, the refusal to run without a TPU, and a tiny
cell driven end to end through the harness's internals.

  python -m pytest bench -q
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402
import traffic  # noqa: E402
import xplane  # noqa: E402


def tiny_cell(name="qwen2-vl-2b.chat", **mix_kw) -> harness.Cell:
    """A cell of the benchmark (or one kept under ``bench/`` for a later
    one, named ``<config>.<mix>``) at a size the CPU runs in seconds."""
    spec = harness.load_spec()
    if name in {w["name"] for w in spec["workloads"]}:
        cell = harness.find_cell(spec, name)
    else:
        conf, mix = name.split(".", 1)
        cell = harness.Cell(name, 1, json.loads(
            (BENCH / "configs" / f"{conf}.json").read_text()), json.loads(
            (BENCH / "traffic" / f"{mix}.json").read_text()), [], [])
    cell.config = dict(
        cell.config, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        vocab_size=512, batch_slots=4, max_seq=256,
        rope_scaling={"type": "mrope", "mrope_section": [4, 6, 6]})
    mix = dict(cell.mix, warmup_s=min(1.0, cell.mix.get("warmup_s", 0)))
    for k in ("prompt", "output"):
        if mix[k]["dist"] == "lognormal":
            mix[k] = dict(mix[k], median=24, min=4, max=64)
        elif mix[k]["dist"] == "uniform":
            mix[k] = dict(mix[k], min=8, max=24 if k == "output" else 96)
    if mix["loop"] == "open":
        mix["rate_per_s"] = 3.0
    mix.update(mix_kw)
    cell.mix = mix
    return cell


def run_tiny(cell, seed=12345678901, seconds=2.0, trace=False,
             impl="auto", controls=()):
    import jax
    devs = jax.devices()
    peaks = {devs[0].device_kind: {"bf16_flops_per_s": 1e12,
                                   "hbm_bytes_per_s": 1e11}}
    return harness.run_cell(cell, seed, seconds, trace,
                            t_process=time.monotonic(), devs=devs,
                            peaks=peaks, impl=impl, controls=controls)


# -- trace reduction ----------------------------------------------------------

def test_union_and_gaps():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.gaps([(0, 3), (5, 8)], 0, 10) == [(3, 5), (8, 10)]
    assert xplane.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_reduce_synthetic_trace():
    s = 1e9   # ns
    ops = {0: [("%while.3 = (s32[]) while(...)", {}, 0, 3 * s),
               ("%fusion.1 = f32[8] fusion(...)", {}, 0, 2 * s),
               ("%spx_kernel.7 = f32[8] custom-call(...)", {}, 1 * s, 3 * s),
               ("%fusion.2 = f32[8] fusion(...)", {}, 6 * s, 7 * s)],
           1: [("%fusion.1 = f32[8] fusion(...)", {}, 0, 1 * s)]}
    spans = [("bench.step", 0, 5 * s), ("bench.submit", 3.5 * s, 5 * s),
             ("bench.step", 5.5 * s, 9 * s)]
    red = xplane.reduce(ops, spans, 0, 10 * s)
    # device 0 busy [0,3) + [6,7) = 4 s, device 1 busy 1 s: mean 2.5
    assert red["busy_s"] == pytest.approx(2.5)
    assert red["window_s"] == pytest.approx(10.0)
    # a loop that contains other ops is busy time, not an op of its own
    assert set(red["op_s"]) == {"fusion", "spx_kernel"}
    assert red["op_s"]["fusion"] == pytest.approx(4.0)
    assert red["op_s"]["spx_kernel"] == pytest.approx(2.0)
    idle = dict((n, v) for n, v in red["idle_gaps"])
    # device 0: [3,6) mid 4.5 in submit, [7,10) mid 8.5 in step;
    # device 1: [1,10) mid 5.5 in the second step
    assert idle["bench.submit"] == pytest.approx(3.0)
    assert idle["bench.step"] == pytest.approx(3.0 + 9.0)


def test_roofline_reads_matched_events():
    s = 1e9
    red = xplane.reduce({0: [("x", {"long_name": "a spx_matmul call"}, 0,
                              2 * s)]}, [], 0, 4 * s)
    call = {"m": 1.0, "k": 1000, "n": 1000, "bits": 4, "layers": 1}
    peak = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}
    share, bound = harness.roofline("spx_matmul", [call], red, peak)
    flops, nbytes = harness.plugin("work", "spx_matmul").work(call)
    assert bound == "memory"
    assert share == pytest.approx(100 * nbytes / 1e6 / 2.0)
    assert harness.roofline("paged_decode_ragged", [{"attended": [1.0],
                            "hq": 1, "hkv": 1, "dh": 1, "layers": 1}],
                            red, peak) == (None, None)


# -- work functions -----------------------------------------------------------

def test_kernel_work_counts_window_rows():
    from types import SimpleNamespace as NS
    cell = tiny_cell()
    ticks = [harness.Tick(float(i), i + 0.9) for i in range(6)]
    # prompt of 8 admitted at tick 0, first token at the end of tick 1,
    # then one decode token per tick 2..5
    req = NS(prompt=np.ones(8, np.int32))
    tr = harness.Track(req, due=0.0, admitted=0.0,
                       times=[1.9, 2.9, 3.9, 4.9, 5.9])
    drv = NS(ticks=ticks, tracks={0: tr})
    w = harness.kernel_work(drv, cell.config, t_open=1.0, t_close=6.0)
    mm = harness.model_module(cell.config)
    per_tick = len(mm.matmuls(cell.config))
    # window ticks 1..5: tick 1 carries half the prompt (4 rows), ticks
    # 2..5 one decode row each, attending 9..12 tokens
    assert len(w["spx_matmul"]) == 5 * per_tick
    assert sorted({c["m"] for c in w["spx_matmul"]}) == [1.0, 4.0]
    assert [c["attended"] for c in w["paged_attention"]] == \
        [[9.0], [10.0], [11.0], [12.0]]
    want = (8.0 * mm.token_flops(cell.config)
            + 5 * mm.head_flops(cell.config)
            + mm.attention_flops(cell.config, 8 * 8 / 2 / 2 + 42))
    assert w["flops"] == pytest.approx(want)

def test_check_sample_spreads_over_requests():
    from types import SimpleNamespace as NS
    tracks = {i: harness.Track(NS(prompt=np.full(5 + i, 7, np.int32),
                                  output=list(range(1, 1 + 20 * (i + 1)))),
                               due=0.0) for i in range(12)}
    sample = harness.sample_for_check(NS(tracks=tracks), seed=2 ** 35)
    assert len(sample) == harness.SAMPLE_REQUESTS
    assert sample[0] is tracks[11]           # the longest is always in it
    assert len({id(t) for t in sample}) == len(sample)
    seqs, rows, toks = harness.served_rows(sample)
    per = harness.TOKENS_PER_REQUEST
    for i, tr in enumerate(sample):
        p, o = len(tr.req.prompt), tr.req.output
        pos = [j for s, j in rows if s == i]
        # first and last served token, at most ``per`` of them, each read
        # at the position whose logits chose it
        assert pos[0] == p - 1 and pos[-1] == p + len(o) - 2
        assert len(pos) == min(per, len(o))
        assert len(seqs[i]) == pos[-1] + 1
        assert [int(seqs[i][j + 1]) for j in pos[:-1]] == \
            [int(t) for t, (s, _) in zip(toks, rows) if s == i][:-1]
    assert len(rows) <= harness.SAMPLE_REQUESTS * per


def test_spx_matmul_work_hand_count():
    w = harness.plugin("work", "spx_matmul").work
    flops, nbytes = w({"m": 64, "k": 1536, "n": 8960, "bits": 4,
                       "layers": 28})
    assert flops == 28 * 2 * 64 * 1536 * 8960
    codes = 1536 * 8960 // 2
    assert nbytes == 28 * (codes + 4 * 8960 + 4 * 64 * 1536 + 4 * 64 * 8960)


@pytest.mark.parametrize("kernel,kv_side", [
    ("paged_decode_ragged", 128 * 4), ("paged_decode_ragged_quant", 128 + 4)])
def test_paged_decode_work_hand_count(kernel, kv_side):
    w = harness.plugin("work", kernel).work
    call = {"attended": [100.0, 300.0], "hq": 12, "hkv": 2, "dh": 128,
            "layers": 28}
    flops, nbytes = w(call)
    assert flops == 28 * 4 * 12 * 128 * 400
    assert nbytes == 28 * (2 * 400 * 2 * kv_side + 2 * 2 * 12 * 128 * 4)


# -- traffic ------------------------------------------------------------------

@pytest.mark.parametrize("mix_name", ["chat", "longgen", "longprompt",
                                      "chat-closed"])
def test_traffic_same_seed_same_requests(mix_name):
    mix = json.loads((BENCH / "traffic" / f"{mix_name}.json").read_text())
    kw = dict(seconds=30, clients=8, max_seq=2048, vocab=151936)
    a = traffic.generate(mix, 2 ** 33 + 5, **kw)
    b = traffic.generate(mix, 2 ** 33 + 5, **kw)
    c = traffic.generate(mix, 7, **kw)
    flat = (lambda x: x if mix["loop"] == "open"
            else [it for q in x for it in q])
    a, b, c = flat(a), flat(b), flat(c)
    assert [(it.prompt.tolist(), it.max_new_tokens, it.due) for it in a] \
        == [(it.prompt.tolist(), it.max_new_tokens, it.due) for it in b]
    # another seed: the same set of sizes in another order, other tokens
    for size in (lambda it: len(it.prompt), lambda it: it.max_new_tokens):
        assert sorted(map(size, a)) == sorted(map(size, c))
    assert [len(it.prompt) for it in a] != [len(it.prompt) for it in c]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    for it in a:
        assert len(it.prompt) + it.max_new_tokens <= 2048
        assert it.prompt.min() >= 1 and it.prompt.max() < 151936


def test_open_loop_window_holds_rate_times_seconds():
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    gaps = []
    for seed in (3, 2 ** 40):
        items = traffic.generate(mix, seed, seconds=30, clients=0,
                                 max_seq=2048, vocab=1000)
        due = np.array([it.due for it in items])
        assert due[0] == 0 and np.all(np.diff(due) > 0)
        # the window's gaps (the last one up to its close): the same set
        # for every seed, in the seed's own order
        w_due = np.append(due[due >= mix["warmup_s"]], mix["warmup_s"] + 30)
        gaps.append(np.diff(w_due))
        w = mix["warmup_s"]
        in_window = np.sum((due >= w) & (due < w + 30))
        assert in_window == round(mix["rate_per_s"] * 30)
        assert np.sum(due < w) == round(mix["rate_per_s"] * w)
    assert np.allclose(np.sort(gaps[0]), np.sort(gaps[1]))
    assert not np.allclose(gaps[0], gaps[1])


# -- discovery by name --------------------------------------------------------

def test_new_config_mix_metric_and_kernel_are_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    b = root / "bench"
    conf = json.loads((b / "configs" / "qwen2-vl-2b.json").read_text())
    (b / "configs" / "dummy-model.json").write_text(json.dumps(
        dict(conf, num_hidden_layers=4)))
    (b / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"loop": "closed", "prompt": {"dist": "uniform", "min": 8,
                                      "max": 16},
         "output": {"dist": "uniform", "min": 4, "max": 8}}))
    (b / "metrics" / "dummy_metric.py").write_text(
        "def compute(rec):\n    return rec['rec']['tokens'] * 2\n")
    (b / "work" / "dummy_kernel.py").write_text(
        "MATCH = ['dummy']\n"
        "def work(call):\n    return 2.0 * call['m'], 4.0 * call['m']\n")
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "dummy-model", "source": "x",
                            "file": "bench/configs/dummy-model.json",
                            "reduced": ["num_hidden_layers"], "why": "x"})
    spec["workloads"].append({"name": "dummy-model.dummy-mix",
                              "config": "dummy-model",
                              "traffic": "dummy-mix", "chips": 1,
                              "why": "x"})
    spec["per_layer"].append({"name": "dummy_metric", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "x", "moves": "output_tok_per_s",
                              "workloads": ["dummy-model.dummy-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.find_cell(harness.load_spec(root),
                             "dummy-model.dummy-mix", root)
    assert cell.config["num_hidden_layers"] == 4
    assert cell.mix["loop"] == "closed"
    assert [m["name"] for m in cell.per_layer][-1] == "dummy_metric"
    metric = harness.plugin("metrics", "dummy_metric", b)
    assert metric.compute({"rec": {"tokens": 21}}) == 42
    kernel = harness.plugin("work", "dummy_kernel", b)
    assert kernel.work({"m": 3}) == (6.0, 12.0)
    # nothing that was there before changed
    assert all(p.read_bytes() == data for p, data in before.items())


# -- no TPU, no result --------------------------------------------------------

def test_run_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "qwen2-vl-2b.chat", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax

    class Fake:
        platform, device_kind = "tpu", "TPU v99"
    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    with pytest.raises(SystemExit, match="no peaks"):
        harness.check_device(1, {"TPU v5 lite": {}})


def test_run_exits_nonzero_in_a_bare_directory(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-vl-2b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# -- the program's weights and a tiny cell end to end -------------------------

def test_program_params_follow_the_program_tree():
    import jax
    from repro.models import lm as lm_mod
    cell = tiny_cell()
    arch = harness.arch_config(cell.config)
    want = jax.eval_shape(lambda: lm_mod.lm_init(jax.random.PRNGKey(0),
                                                 arch))
    got = harness.model_module(cell.config).program_params(
        cell.config, 3, lambda t: t)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype


@pytest.mark.parametrize("trace", [False, True])
def test_tiny_cell_runs_and_is_correct(monkeypatch, trace):
    monkeypatch.setattr(harness, "TRACE_DIR",
                        ROOT / ".bench_trace" / f"test{int(trace)}")
    res = run_tiny(tiny_cell(), trace=trace, impl="interpret")
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    names = set(res["metrics"])
    if trace:
        assert {"tick_ms_p50", "step_mfu_pct", "window_compiles"} <= names
        assert 0 < res["metrics"]["step_mfu_pct"]["value"] < 100
        assert res["metrics"]["window_compiles"]["value"] == 0
        assert "busy_s" in res["device"]
    else:
        assert {"itl_p95_ms", "setup_s"} == names


def test_tiny_closed_loop_fill_cell_is_correct():
    res = run_tiny(tiny_cell("qwen2-vl-2b-spxkv.longgen"))
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {"output_tok_per_s", "itl_p95_ms",
                                   "setup_s"}
    assert res["attempted"] == 4


ROUTER_CHILD = """
import json, sys
sys.path.insert(0, {bench!r})
import jax, harness, test_harness
assert len(jax.devices()) == 4
res = test_harness.run_tiny(test_harness.tiny_cell(
    "qwen2-vl-2b-r4.chat-closed"))
print(json.dumps({{"correct": res["correct"], "attempted": res["attempted"],
                  "count": res["device"]["count"]}}))
"""


def test_router_cell_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c",
                        ROUTER_CHILD.format(bench=str(BENCH))],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res == {"correct": True, "attempted": res["attempted"],
                   "count": 4}
    assert res["attempted"] >= 16
