#!/usr/bin/env python3
"""Offer an open-loop cell's mix at several rates, to find the highest rate
the system sustains (the knee), once, on the chip.

    python3 benchmarks/chip/sweep.py --workload smollm-chat --rates 1,2,4,8 --seconds 15

One process: the set-up and warm-up of ``run.py``, then for each rate a
window of ``--seconds`` at that rate, waiting for every request to finish
before the next.  Prints one JSON line per rate: offered and delivered
output tokens per second, TTFT and inter-token percentiles, and the
backlog at the window's close (requests due in the window that had no
token yet).  The knee and its readings are written into the mix file by
hand; no run of the benchmark searches for a rate.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as bench
import stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    args = bench.parse(["--workload", a.workload, "--seed", str(a.seed), "--seconds",
                        str(a.seconds)] + (["--rehearse"] if a.rehearse else []))
    try:
        bench.prepare(args)
        run = bench.Run(args)
        run.build()
        run.warm()
        for k, rate in enumerate(float(x) for x in a.rates.split(",")):
            run.tag = f"s{k}."
            run.cell.mix["arrivals"]["rate_per_s"] = rate
            run.rec.reset()
            for d in (run.sent, run.reqs):
                d.clear()
            run.order.clear()
            run.offer()
            run.rec.wait(lambda: all(run.rec.finished(r) for r in run.sent),
                         bench.DRAIN_TIMEOUT_S, "every request of the rate")
            lo, hi = run.t_open, run.t_close
            times = run.rec.times
            due = run.in_window()
            ttft = [times[r][0] - run.sent[r] if r in times else float("inf") for r in due]
            gaps = [b - a for ts in times.values() for a, b in zip(ts, ts[1:])
                    if lo <= a and b < hi]
            backlog = sum(1 for r in due if r not in times or times[r][0] >= hi)
            offered = sum(run.reqs[r].max_new for r in due) / (hi - lo)
            row = {
                "rate_per_s": rate, "due": len(due), "offered_tok_s": offered,
                "output_tok_s": sum(1 for ts in times.values() for t in ts if lo <= t < hi) / (hi - lo),
                "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
                "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90),
                "itl_p50_ms": 1e3 * stats.percentile(gaps, 50) if gaps else None,
                "itl_p99_ms": 1e3 * stats.percentile(gaps, 99) if gaps else None,
                "backlog_at_close": backlog,
                "errors": len(run.rec.error),
                "wake_late_max_ms": 1e3 * max(run.wake_late)[0],
            }
            print(json.dumps(row), flush=True)
        run.server.close_intake()
        run.server.join(bench.DRAIN_TIMEOUT_S)
        run.server.stop()
    except bench.Fail as e:
        bench.log(f"FAIL {e}")
        return 1
    finally:
        if "run" in locals():
            bench.shutil.rmtree(run.tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
