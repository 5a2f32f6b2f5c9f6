#!/usr/bin/env python3
"""Spreads of a cell's end-to-end metrics over sets of runs, and the bound
each would take.

    python3 benchmarks/chip/bounds.py A='out/chat_A_*.out' B='out/chat_B_*.out'

Each set is a glob of files that hold one run's standard output each; the
last line is the run's result.  Per metric it prints each set's median and
spread (the interquartile distance over the median, ``stats.spread``), the
mean of the sets' spreads with each set's run farthest from its median
left out, the spread of every run together, and five times the widest
set's spread, never under 1%.  A bound has to lie above twice the second
and under eight times the third.
"""
from __future__ import annotations

import glob
import json
import statistics
import sys

import stats


def load(pattern: str) -> list[dict]:
    out = []
    for path in sorted(glob.glob(pattern)):
        with open(path) as f:
            lines = f.read().strip().splitlines()
        if lines:
            out.append({k: v["value"] for k, v in json.loads(lines[-1])["metrics"].items()})
    return out


def trimmed(values: list[float]) -> list[float]:
    med = statistics.median(values)
    rest = list(values)
    rest.remove(max(values, key=lambda x: abs(x - med)))
    return rest


def main(argv: list[str]) -> int:
    sets = {}
    for arg in argv:
        name, _, pattern = arg.partition("=")
        sets[name] = load(pattern)
    names = sorted({k for runs in sets.values() for r in runs for k in r})
    for m in names:
        per = {s: [r[m] for r in runs if m in r] for s, runs in sets.items()}
        per = {s: v for s, v in per.items() if len(v) >= 3}
        if not per:
            continue
        widest = max(stats.spread(v) for v in per.values())
        trim = statistics.mean(stats.spread(trimmed(v)) for v in per.values())
        every = stats.spread([x for v in per.values() for x in v])
        sets_txt = "; ".join(f"{s} median {statistics.median(v):.4f} spread {stats.spread(v):.4f}"
                             for s, v in per.items())
        print(f"{m}: {sets_txt}; trimmed {trim:.4f}; all runs {every:.4f}; "
              f"5x widest {max(5 * widest, 0.01):.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
