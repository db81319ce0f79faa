"""The ``jamba2-mini`` configuration through the harness on the CPU, at a
reduced width (every key but the widths as the cell has it): it builds,
serves its traffic and comes out ``correct`` by ``harness.run_cell``'s
check, while the float8 control and a planted fault (one expert's codes
perturbed) come out not correct.

  python -m pytest bench -q
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
from test_harness import run_tiny  # noqa: E402


def tiny_jamba() -> harness.Cell:
    """``bench/configs/jamba2-mini.json`` under ``bench/traffic/
    chat-closed.json`` at hidden 128 (4 query heads and 2 KV heads of
    32), expert width 256, a 512-token vocabulary and 4 slots; the
    period, the 16 experts, the Mamba sizes, the limit and the traffic's
    shape as in the files. On the CPU the program's f32 matmuls and the
    reference's agree to f32 rounding, so the program reads 0.0 on the
    seed below and the float8 control 1.76."""
    with open(BENCH / "configs" / "jamba2-mini.json") as f:
        config = json.load(f)
    with open(BENCH / "traffic" / "chat-closed.json") as f:
        mix = json.load(f)
    spec = harness.load_spec()
    config.update(hidden_size=128, intermediate_size=256,
                  num_attention_heads=4, num_key_value_heads=2,
                  vocab_size=512, batch_slots=4, max_seq=256)
    cell = harness.Cell(
        "jamba2-mini.chat-closed", 1, config, mix,
        [m for m in spec["end_to_end"] if "workloads" not in m],
        [m for m in spec["per_layer"] if "workloads" not in m])
    mix = dict(cell.mix, warmup_s=1.0)
    for k in ("prompt", "output"):
        mix[k] = dict(mix[k], median=24, min=4, max=64)
    cell.mix = mix
    return cell


def test_jamba_cell_is_correct_and_its_control_is_not():
    res = run_tiny(tiny_jamba(), seed=2 ** 33 + 5, controls=("fp8",))
    program = res["compared"]["logit_gap_max"]
    control = res["controls"]["fp8"]["compared"]["logit_gap_max"]
    assert res["correct"], (program, control)
    assert res["controls"]["fp8"]["correct"] is False, (program, control)
    assert program["value"] <= program["limit"] < control["value"]


def test_perturbed_expert_makes_the_run_incorrect(monkeypatch):
    """Expert 0 of the first MoE layer served with every code changed."""
    real = harness.model_module

    def faulty(config):
        mm = real(config)
        made = mm.program_params

        def program_params(c, seed, quantize):
            params = made(c, seed, quantize)
            moe = next(s["moe"] for s in params["stack"]["slots"]
                       if "moe" in s)
            for name in ("gate", "up", "down"):
                qt = moe[name]
                qt.codes = qt.codes.at[:, 0].set(qt.codes[:, 0] ^ 0x77)
            return params
        mm.program_params = program_params
        return mm

    monkeypatch.setattr(harness, "model_module", faulty)
    res = run_tiny(tiny_jamba(), seed=2 ** 33 + 5)
    gap = res["compared"]["logit_gap_max"]
    assert res["correct"] is False, gap
    assert gap["value"] > gap["limit"]
