"""Run one benchmark cell once on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chips. It checks that the devices are TPUs
of a kind in ``bench/peaks.json`` (anything else exits non-zero with no
result), makes the configuration's weights from the seed, warms every
shape the traffic uses, drives the traffic for ``--seconds``, checks the
served tokens against the plain reference, and prints one JSON object as
the last line of standard output: the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics (from a profiler trace of the
window) with ``--trace 1``. Set-up (``setup_s``) runs from process start
until the window opens. Compiled programs persist in ``.jax_cache`` at
the root of the checkout.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402


def main(argv=None, controls=()) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # libtpu would otherwise keep its logs under a fixed path in /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    with open(BENCH / "peaks.json") as f:
        peaks = json.load(f)
    harness.enable_cache()
    devs = harness.check_device(cell.chips, peaks)
    harness.log(f"{cell.name}: {len(devs)} x {devs[0].device_kind}, seed "
                f"{args.seed}, {args.seconds} s window, trace {args.trace}")
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_process=T_PROCESS,
                              devs=devs, peaks=peaks, controls=controls)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
