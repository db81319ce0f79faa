"""The comparison that decides ``correct`` has to fail what it exists to
catch, at a size a CPU test run holds:

- the control: in a whole run, the reference put in the program's place
  with its matmul operands rounded to float8 is judged by the same check
  at the same positions, and comes out not correct while the program
  comes out correct;
- a fault planted under the timed path (a served token altered where the
  engine produces it) turns a whole run's ``correct`` false.

  python -m pytest bench -q
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from test_harness import run_tiny, tiny_cell  # noqa: E402


@pytest.mark.parametrize("name", ["qwen2-vl-2b.chat",
                                  "qwen2-vl-2b-spxkv.longgen"])
def test_control_through_the_check_is_incorrect(name):
    cell = tiny_cell(name)
    cell.config = dict(cell.config, hidden_size=256, intermediate_size=512,
                       num_hidden_layers=8, vocab_size=4096,
                       num_attention_heads=2,
                       rope_scaling={"type": "mrope",
                                     "mrope_section": [16, 24, 24]})
    res = run_tiny(cell, seed=2 ** 32 + 17, controls=("fp8",))
    program = res["compared"]["logit_gap_max"]
    control = res["controls"]["fp8"]["compared"]["logit_gap_max"]
    assert res["correct"], (program, control)
    assert res["controls"]["fp8"]["correct"] is False, (program, control)
    assert program["value"] <= program["limit"] < control["value"]
    assert list(res)[-2:] == ["controls", "compared"]


def test_altered_token_makes_the_run_incorrect(monkeypatch):
    from repro.serving.engine import ServeEngine
    calls = {"n": 0}
    accept = ServeEngine._accept_tokens

    def altered(self, req, drafts, logits):
        out = accept(self, req, drafts, logits)
        calls["n"] += 1
        if calls["n"] % 7 == 0:
            out[-1] = (out[-1] + 1) % logits.shape[-1]
        return out
    monkeypatch.setattr(ServeEngine, "_accept_tokens", altered)
    res = run_tiny(tiny_cell())
    assert calls["n"] > 7
    assert res["correct"] is False
    gap = res["compared"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]
