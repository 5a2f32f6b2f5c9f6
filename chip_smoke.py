#!/usr/bin/env python3
"""One smoke run of the serving main path on a TPU chip.

    python chip_smoke.py              # one chip: serve, kernel-vs-jnp, train
    python chip_smoke.py --chips 4    # four one-chip engines behind the router

With no option it drives smollm-135m at its published widths (30 layers,
d 576, 9 heads over 3 KV heads, vocab 49,152; random weights from
``--seed``) through the entry points a user calls, in one process:

  (a) print the device JAX found; anything but a TPU fails the run;
  (b) build a paged ``ServeEngine`` with the Pallas kernels on (page 16,
      4 slots, ``max_len`` 512) and check that its decode step and its
      prefill lower to Mosaic kernels (``tpu_custom_call``);
  (c) serve 8 seeded requests (32-128 prompt tokens, 16 new tokens each)
      through the request stream and a ``ServeClient``;
  (d) check every request was answered, the streamed deltas equal each
      completion, and no page is in use at exit;
  (e) compare the kernel path with the jnp path on the same chip: the
      first decode step's log-probabilities within a stated tolerance, and
      how many greedy tokens the two engines' transcripts share;
  (f) take three ``Trainer`` steps (batch 8, seq 512) and check the
      losses are finite.

``--chips N`` runs only the fleet path: one engine alone on chip 0 serves
16 requests through the router, then a ``Fleet`` of N engine processes,
engine ``i`` pinned to chip ``i``, serves the same 16; every transcript
must match.  This process never starts a JAX backend on that path, so the
chips stay free for the engines.

Timings printed on the way are those of this one smoke run, not a
benchmark.  The last line of standard output is one JSON object, printed
only when every check passed::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--rehearse`` runs the same phases with the reduced same-family config on
whatever backend JAX has (for a CPU rehearsal); it never prints that line
and always exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "smollm-135m"
SLOTS, MAX_LEN, PAGE = 4, 512, 16
N_REQUESTS, MAX_NEW = 8, 16
FLEET_REQUESTS, FLEET_PROMPT = 16, 96
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 8, 512
# First decode step, kernel path vs jnp path, as log-probabilities over the
# whole vocabulary.  Both run bf16 operands with f32 accumulation; they
# differ in where values are rounded to bf16 (the kernels keep scores and
# softmax weights in f32, the jnp path feeds bf16 weights to the PV
# product), a relative 2^-9 per rounding that 30 residual layers carry
# into the logits.  0.1 nats bounds that drift and is far below what a
# wrong mask, page or head mapping gives (differences of whole nats).
LOGPROB_ATOL = 0.1


class Smoke:
    """Phase runner: a failed phase is recorded and the run goes on with
    the phases that do not need it; any failure makes the exit non-zero."""

    def __init__(self):
        self.failures: list[str] = []

    def check(self, ok: bool, what: str) -> bool:
        print(f"[smoke] {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failures.append(what)
        return ok

    def phase(self, name: str, fn, *args):
        print(f"[smoke] --- {name}", flush=True)
        try:
            return fn(*args)
        except Exception:  # noqa: BLE001 - recorded, and the exit fails
            traceback.print_exc()
            self.failures.append(f"{name}: raised")
            return None


def seeded_prompts(vocab: int, n: int, seed: int, length=None):
    import numpy as np

    rng = np.random.default_rng(seed)
    lens = (
        [length] * n if length is not None else rng.integers(32, 129, n).tolist()
    )
    return [rng.integers(1, vocab, int(L)).astype(np.int32) for L in lens]


def serve_prompts(engine, prompts, tag: str):
    """Send every prompt through the request stream, run the engine until
    the stream closes, and collect the response stream with a client."""
    from repro.core.connectors import new_key
    from repro.core.store import Store
    from repro.core.streaming import (
        QueuePublisher,
        QueueSubscriber,
        StreamConsumer,
        StreamProducer,
    )
    from repro.serve.client import ServeClient

    ns = f"smoke-{tag}-{new_key()}"
    producer = StreamProducer(QueuePublisher(ns), {"requests": Store(f"{ns}-req")})
    consumer = StreamConsumer(QueueSubscriber("requests", ns), timeout=600.0)
    resp_producer = StreamProducer(
        QueuePublisher(ns), {"responses": Store(f"{ns}-resp")}
    )
    client = ServeClient(
        StreamConsumer(QueueSubscriber("responses", ns), timeout=600.0)
    )
    errors: list[BaseException] = []

    def collect():
        try:
            client.collect(deadline=900.0)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    collector = threading.Thread(target=collect, daemon=True)
    collector.start()
    for i, prompt in enumerate(prompts):
        producer.send(
            "requests", {"prompt": prompt},
            metadata={"req_id": f"{tag}{i}", "max_new_tokens": MAX_NEW},
        )
    producer.flush_topic("requests")
    producer.close_topic("requests")
    t0 = time.perf_counter()
    completed = engine.run(consumer, resp_producer)
    wall = time.perf_counter() - t0
    collector.join(timeout=60)
    if errors:
        raise errors[0]
    if collector.is_alive():
        raise RuntimeError("response client did not drain after engine exit")
    return completed, client, wall


def check_served(smoke: Smoke, engine, completed, client, prompts, tag: str):
    ids = [f"{tag}{i}" for i in range(len(prompts))]
    n_done = sum(
        1 for r in ids
        if r in client.results and client.results[r].result is not None
    )
    smoke.check(
        n_done == len(prompts) and sorted(completed) == sorted(ids),
        f"{n_done}/{len(prompts)} requests answered",
    )
    smoke.check(
        all(len(completed[r]["tokens"]) == MAX_NEW for r in ids if r in completed),
        f"every completion holds {MAX_NEW} tokens",
    )
    smoke.check(
        all(
            client.results[r].stream_tokens == client.results[r].result["tokens"]
            for r in ids
            if r in client.results and client.results[r].result is not None
        ),
        "streamed deltas equal each final completion",
    )
    in_use = engine.pages.pages_in_use()
    smoke.check(in_use == 0, f"{in_use} pages in use at exit")
    return [completed[r]["tokens"] for r in ids if r in completed]


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------


def one_chip(args) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, get_smoke_config
    from repro.dist.sharding import materialize_params
    from repro.launch.compile_cache import use_compile_cache
    from repro.models.api import build_model
    from repro.serve.engine import ServeEngine, serve_context

    smoke = Smoke()
    cache_dir = use_compile_cache()
    # (a) the device
    devs = jax.devices()
    dev = devs[0]
    print(
        f"[smoke] device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__} compile cache={cache_dir}",
        flush=True,
    )
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"[smoke] FAIL no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 1

    # (b) model and engines
    cfg = (get_smoke_config if args.rehearse else get_config)(ARCH)
    print(
        f"[smoke] {cfg.name}: {cfg.n_layers} layers, d {cfg.d_model}, "
        f"{cfg.n_heads} heads / {cfg.n_kv_heads} KV heads, head dim "
        f"{cfg.head_dim_}, vocab {cfg.vocab}; seed {args.seed}",
        flush=True,
    )
    ctx_k = serve_context(cfg, use_kernels=True, page_size=PAGE)
    model_k = build_model(ctx_k)
    with ctx_k.mesh:
        params = materialize_params(
            model_k.param_specs(), jax.random.PRNGKey(args.seed)
        )
    engine = ServeEngine(
        ctx_k, params, slots=SLOTS, max_len=MAX_LEN, page_size=PAGE, eos_id=-1
    )
    smoke.check(engine.paged, "engine runs the paged pool")
    prompts = seeded_prompts(cfg.vocab, N_REQUESTS, args.seed)
    print(f"[smoke] prompt lengths {[len(p) for p in prompts]}", flush=True)

    # one decode step's arguments: every slot at 128 tokens (the longest
    # prompt), the block table as wide as serving them takes; every entry
    # is the null page, so the step writes scratch only
    width = engine._bt_width(engine.pages.pages_needed(128 + MAX_NEW))
    bt = jnp.full((SLOTS, width), engine._null_page, jnp.int32)
    tok = jnp.ones((SLOTS, 1), jnp.int32)
    at128 = jnp.full((SLOTS,), 128, jnp.int32)

    def kernels_and_compile():
        engine._ensure_cache()
        for name, fn, fargs in (
            (f"decode step ({SLOTS} slots, width {width})", engine._decode,
             (params, engine._cache, bt, tok, at128)),
            ("prefill (1 x 128 tokens)", engine._prefill,
             (params, jnp.ones((1, 128), jnp.int32))),
        ):
            lowered = fn.lower(*fargs)
            has = "tpu_custom_call" in lowered.as_text()
            smoke.check(
                has == on_tpu,
                f"{name} {'lowers to' if has else 'has no'} Pallas kernel "
                "(tpu_custom_call)",
            )
            t0 = time.perf_counter()
            lowered.compile()
            print(f"[smoke] compile {name}: {time.perf_counter() - t0:.3f} s",
                  flush=True)

    smoke.phase("(b) kernels in the compiled path", kernels_and_compile)

    # (c) + (d) serve through the stream path
    def serve_kernel():
        completed, client, wall = serve_prompts(engine, prompts, "k")
        print(
            f"[smoke] served {len(completed)} requests in {wall:.3f} s "
            f"(compiles included); decode steps {engine.metrics['decode_steps']}, "
            f"prefills {engine.metrics['prefills']}; peak_bytes_in_use "
            f"{peak_bytes(dev)}",
            flush=True,
        )
        return check_served(smoke, engine, completed, client, prompts, "k")

    tokens_k = smoke.phase("(c, d) serve 8 requests, kernels on", serve_kernel)

    def decode_step_time():
        engine._cache, logits = engine._decode(params, engine._cache, bt, tok, at128)
        logits.block_until_ready()  # warm-up
        n = 20
        t0 = time.perf_counter()
        for _ in range(n):
            engine._cache, logits = engine._decode(
                params, engine._cache, bt, tok, at128
            )
        logits.block_until_ready()
        print(
            f"[smoke] decode step after warm-up ({SLOTS} slots at 128 tokens, "
            f"width {width}): {(time.perf_counter() - t0) / n:.6f} s/step "
            f"over {n}",
            flush=True,
        )

    smoke.phase("decode step time", decode_step_time)

    # (e) kernel path vs jnp path on the same chip
    def kernel_vs_jnp():
        ctx_j = serve_context(cfg, use_kernels=False, page_size=PAGE)
        model_j = build_model(ctx_j)
        batch = prompts[:SLOTS]
        sp = max(len(p) for p in batch)
        toks = np.zeros((len(batch), sp), np.int32)
        for i, p in enumerate(batch):
            toks[i, : len(p)] = p
        lens = jnp.asarray([len(p) for p in batch], jnp.int32)
        toks = jnp.asarray(toks)

        def first_steps(model, first=None):
            logits0, cache = jax.jit(
                lambda p, t, ln: model.prefill_batch(p, t, ln, MAX_LEN)
            )(params, toks, lens)
            logits0 = logits0[:, : cfg.vocab].astype(jnp.float32)
            if first is None:
                first = jnp.argmax(logits0, axis=-1).astype(jnp.int32)
            logits1, _ = jax.jit(model.verify_batch)(
                params, cache, first[:, None], lens
            )
            logits1 = logits1[:, 0, : cfg.vocab].astype(jnp.float32)
            return (
                jax.nn.log_softmax(logits0), jax.nn.log_softmax(logits1), first
            )

        lp0_k, lp1_k, first = first_steps(model_k)
        lp0_j, lp1_j, _ = first_steps(model_j, first)  # same fed token
        d0 = float(jnp.max(jnp.abs(lp0_k - lp0_j)))
        d1 = float(jnp.max(jnp.abs(lp1_k - lp1_j)))
        print(
            f"[smoke] kernel vs jnp, max |d log p| over {len(batch)} rows x "
            f"{cfg.vocab}: prefill {d0:.6f}, first decode step {d1:.6f} "
            f"(tolerance {LOGPROB_ATOL})",
            flush=True,
        )
        smoke.check(
            d1 <= LOGPROB_ATOL and d0 <= LOGPROB_ATOL,
            f"first decode step log-probs agree within {LOGPROB_ATOL}",
        )
        engine_j = ServeEngine(
            ctx_j, params, slots=SLOTS, max_len=MAX_LEN, page_size=PAGE,
            eos_id=-1,
        )
        completed, client, wall = serve_prompts(engine_j, prompts, "j")
        print(f"[smoke] jnp engine served {len(completed)} in {wall:.3f} s",
              flush=True)
        tokens_j = check_served(smoke, engine_j, completed, client, prompts, "j")
        engine_j.close()
        if tokens_k is None:
            return
        same = sum(
            int(a == b) for tk, tj in zip(tokens_k, tokens_j)
            for a, b in zip(tk, tj)
        )
        prefix = [
            next((i for i, (a, b) in enumerate(zip(tk, tj)) if a != b), len(tk))
            for tk, tj in zip(tokens_k, tokens_j)
        ]
        total = sum(len(t) for t in tokens_k)
        print(
            f"[smoke] greedy tokens equal, kernel vs jnp engine: {same}/{total}; "
            f"identical prefix per request {prefix}",
            flush=True,
        )

    smoke.phase("(e) kernel path vs jnp path", kernel_vs_jnp)
    engine.close()

    # (f) three trainer steps
    def train():
        from repro.data.pipeline import SyntheticCorpus
        from repro.launch.mesh import make_host_mesh, rules_for
        from repro.models.layers import ModelContext
        from repro.optim.adamw import AdamWConfig
        from repro.train.trainer import Trainer, TrainerConfig

        mesh = make_host_mesh()
        ctx = ModelContext(cfg, mesh, rules_for(mesh))
        with tempfile.TemporaryDirectory(prefix="smoke-ckpt-") as ckpt:
            trainer = Trainer(ctx, TrainerConfig(
                opt=AdamWConfig(warmup_steps=1), ckpt_every=10**9,
                ckpt_dir=ckpt, log_every=1,
            ))
            trainer.init_state(seed=args.seed)
            corpus = SyntheticCorpus(cfg, TRAIN_BATCH, TRAIN_SEQ, seed=args.seed)
            batches = (corpus.next_batch(i) for i in range(TRAIN_STEPS))
            t0 = time.perf_counter()
            history = trainer.train(batches, TRAIN_STEPS)
            wall = time.perf_counter() - t0
        losses = [h["loss"] for h in history]
        print(
            f"[smoke] trainer: {len(history)} steps (batch {TRAIN_BATCH}, seq "
            f"{TRAIN_SEQ}) in {wall:.3f} s (compile included); step s "
            f"{[round(h['sec'], 6) for h in history]}; losses {losses}; "
            f"failures {trainer.failures}; peak_bytes_in_use {peak_bytes(dev)}",
            flush=True,
        )
        smoke.check(
            len(losses) == TRAIN_STEPS and all(np.isfinite(losses))
            and trainer.failures == 0,
            f"{TRAIN_STEPS} finite trainer losses",
        )

    smoke.phase("(f) trainer steps", train)
    return finish(smoke, args, dev.platform, dev.device_kind, len(devs))


# ---------------------------------------------------------------------------
# N chips: one-chip engines behind the router
# ---------------------------------------------------------------------------


def fleet(args) -> int:
    from repro.configs import get_config, get_smoke_config
    from repro.launch.fleet import Fleet

    smoke = Smoke()
    n = args.chips
    cfg = (get_smoke_config if args.rehearse else get_config)(ARCH)
    prompts = seeded_prompts(cfg.vocab, FLEET_REQUESTS, args.seed, FLEET_PROMPT)
    for key in sorted(k for k in os.environ if k.startswith("TPU_")):
        print(f"[smoke] host env {key}={os.environ[key]}", flush=True)

    def run(n_engines: int, tag: str):
        t0 = time.perf_counter()
        f = Fleet(
            n_engines, toy=False, smoke=args.rehearse, use_kernels=True,
            pin_chips=True, slots=SLOTS, max_len=MAX_LEN, page_size=PAGE,
            ttl=120.0, consumer_timeout=900.0,
        )
        try:
            up = time.perf_counter() - t0
            devices = {name: p.device for name, p in f.procs.items()}
            for name, d in devices.items():
                print(f"[smoke] {tag} engine {name}: {json.dumps(d)}", flush=True)
            t1 = time.perf_counter()
            for i, p in enumerate(prompts):
                f.send(f"q{i}", p, MAX_NEW)
            f.close_intake()
            f.client.collect(deadline=900.0)
            wall = time.perf_counter() - t1
            snap = f.router.snapshot()
            per_engine = {name: 0 for name in f.names}
            for rid in snap:
                per_engine[snap[rid][0]] += 1
            print(
                f"[smoke] {tag}: {n_engines} engine(s) up in {up:.3f} s; "
                f"served {len(f.client.results)} requests in {wall:.3f} s "
                f"(compiles included); per engine {per_engine}; router "
                f"{dict(f.router.metrics)}",
                flush=True,
            )
            results = dict(f.client.results)
        finally:
            f.stop()
        ids = [f"q{i}" for i in range(len(prompts))]
        done = [r for r in ids if r in results and results[r].result is not None]
        smoke.check(
            len(done) == len(prompts), f"{tag}: {len(done)}/{len(prompts)} answered"
        )
        smoke.check(
            all(results[r].stream_tokens == results[r].result["tokens"] for r in done),
            f"{tag}: streamed deltas equal each final completion",
        )
        return {r: results[r].result["tokens"] for r in done}, devices, per_engine

    alone = smoke.phase("one engine alone on chip 0", run, 1, "alone")
    routed = smoke.phase(f"{n} engines behind the router", run, n, "fleet")
    if alone is None or routed is None:
        return finish(smoke, args, None, None, 0)
    (t_alone, _, _), (t_fleet, devices, per_engine) = alone, routed
    same = [r for r in t_alone if t_fleet.get(r) == t_alone[r]]
    smoke.check(
        len(same) == len(prompts),
        f"{len(same)}/{len(prompts)} transcripts match the one-engine run",
    )
    chips = {d.get("visible_chips") for d in devices.values()}
    smoke.check(
        len(chips) == n and all(d["count"] == 1 for d in devices.values()),
        f"{n} engines on {len(chips)} distinct chips ({sorted(map(str, chips))}), "
        "one device each",
    )
    smoke.check(
        sum(1 for v in per_engine.values() if v) == n,
        f"the router sent requests to all {n} engines",
    )
    first = next(iter(devices.values()))
    return finish(smoke, args, first["platform"], first["kind"], len(chips))


def finish(smoke: Smoke, args, platform, kind, count) -> int:
    if smoke.failures:
        print(f"[smoke] FAILED: {smoke.failures}", file=sys.stderr, flush=True)
        return 1
    if args.rehearse or platform != "tpu":
        print(f"[smoke] rehearsal on {platform}: every check passed; "
              "no result is printed off the chip", file=sys.stderr, flush=True)
        return 3
    print(json.dumps(
        {"ok": True, "device": {"platform": platform, "kind": kind, "count": count}}
    ), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("--chips", type=int, default=None,
                    help="run only the fleet path: N one-chip engines behind "
                         "the router against one engine alone")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="reduced config on any backend; never prints a result")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"[smoke] FAIL no {SRC}/repro: run from a checkout of the repo",
              file=sys.stderr)
        return 1
    sys.path.insert(0, SRC)
    return fleet(args) if args.chips else one_chip(args)


if __name__ == "__main__":
    sys.exit(main())
