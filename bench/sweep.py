"""Offered-rate sweep of an open-loop cell, to find the highest rate the
system sustains (its knee) once; the cell's mix then fixes its rate as a
number.

  python3 bench/sweep.py --workload <cell> --rates 1 2 3 4 --seconds 30

One process: build and warm the cell once, then for each rate drive the
mix at that rate (its warm-up, then the window), print one JSON line
(completed tokens/s, TTFT and inter-token p95, requests still queued at
the close), and cancel whatever is still in flight before the next rate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.load_spec(), args.workload)
    with open(BENCH / "peaks.json") as f:
        peaks = json.load(f)
    harness.enable_cache()
    harness.check_device(cell.chips, peaks)
    served = harness.bring_up(cell, args.seed)
    for rate in args.rates:
        mix = dict(cell.mix, rate_per_s=rate)
        drv = harness.drive(served, cell, args.seed, args.seconds, mix=mix)
        drv.next_rid = int(rate * 1e6)
        t_start = time.monotonic()
        t_open = t_start + mix.get("warmup_s", 0)
        drv.run_until(t_start, t_open + args.seconds)
        rec = harness.window_record(drv, t_open, t_open + args.seconds)
        queued = sum(len(e.queue) for e in served.engines)
        e2e = harness.end_to_end(rec, 0.0)
        print(json.dumps({"rate_per_s": rate, "queued_at_close": queued,
                          "due": rec["attempted"],
                          "ticks": len(rec["ticks"]),
                          **{k: e2e.get(k) for k in ("output_tok_per_s",
                                                     "ttft_p95_ms",
                                                     "itl_p95_ms")}}),
              flush=True)
        for rid in list(drv.live):
            served.front.cancel(rid)
        for e in served.engines:
            e.reset_metrics()
    return 0


if __name__ == "__main__":
    sys.exit(main())
