#!/usr/bin/env python3
"""Drive a run with the timed path broken underneath, to show that
``correct`` comes out false.

    python3 benchmarks/chip/faults.py --workload smollm-chat --fault decode_state --rehearse

With ``--rehearse`` (how the self-checks run it) the look for a chip is
skipped and the smoke preset serves; the run is otherwise the benchmark's.
Prints one JSON line: the fault, ``correct`` and the numbers compared.

Faults (planted in the engine's jitted programs):
- ``decode_state``: the decode step returns the page pool unchanged;
- ``insert_state``: the admission insert returns the pool unchanged, so
  the prompt's keys and values never reach it;
- ``half_batch``: the decode step leaves out the odd slots, which get
  their even neighbour's logits;
- ``token``: every decoded token is altered where it is produced (the
  logits shifted by one vocabulary id).
A one-chip cell has no exchange between chips to leave out.
"""
from __future__ import annotations

import argparse
import json
import sys


def _rejit(engine, name: str, fn, donate):
    import jax

    setattr(engine, name, jax.jit(fn, donate_argnums=donate))


def plant(fault: str):
    def decode_state(server):
        e = server.engine
        body = e._decode_paged_body
        _rejit(e, "_decode", lambda p, pool, bt, t, l: (pool, body(p, pool, bt, t, l)[1]), ())

    def insert_state(server):
        e = server.engine
        _rejit(e, "_insert_pages", lambda pool, caches, ids: pool, ())

    def half_batch(server):
        import jax.numpy as jnp

        e = server.engine
        body = e._decode_paged_body

        def step(p, pool, bt, t, l):
            pool, logits = body(p, pool, bt, t, l)
            kept = logits[0::2]  # odd slots left out: each takes its even neighbour's row
            return pool, jnp.repeat(kept, 2, axis=0)[: logits.shape[0]]

        _rejit(e, "_decode", step, (1,))

    def token(server):
        import jax.numpy as jnp

        e = server.engine
        body = e._decode_paged_body

        def step(p, pool, bt, t, l):
            pool, logits = body(p, pool, bt, t, l)
            return pool, jnp.roll(logits, 1, axis=-1)

        _rejit(e, "_decode", step, (1,))

    return {f.__name__: f for f in (decode_state, insert_state, half_batch, token)}[fault]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    import run as bench

    args = bench.parse(["--workload", a.workload, "--seed", str(a.seed), "--seconds",
                        str(a.seconds)] + (["--rehearse"] if a.rehearse else []))
    try:
        bench.prepare(args)
        run = bench.Run(args, fault=plant(a.fault))
        try:
            out = run.execute()
        finally:
            bench.shutil.rmtree(run.tmp, ignore_errors=True)
    except bench.Fail as e:  # a run that cannot finish is not correct either
        bench.log(f"FAIL {e}")
        print(json.dumps({"fault": a.fault, "correct": False, "failed_run": str(e)[:500]}))
        return 0
    print(json.dumps({"fault": a.fault, "correct": out["correct"], "checks": out["checks"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
