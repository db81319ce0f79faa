"""Readings that the limit of the correctness comparison is set from.

  python3 bench/control.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

One run of the cell as ``bench/run.py`` makes it, with the same arguments
and the same result line, whose check also judges the control: the plain
reference with its matmul operands rounded to float8 (e4m3), put in the
program's place at the same positions of the same prompts and served
tokens (``harness.check``). The line gains ``controls``, the control's
numbers beside the program's under ``compared``: the upper and the lower
reading of the limit from one run at the cell's size. The benchmark's
own runs judge no control.
"""
from __future__ import annotations

import sys

import run

if __name__ == "__main__":
    sys.exit(run.main(controls=("fp8",)))
