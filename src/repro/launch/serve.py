"""Serving driver: continuous-batching engine fed by a ProxyStream.

Runs any assigned arch at its published widths (``--smoke`` selects the
reduced same-family config, the one a CPU serves in seconds) under the
``serve`` rules profile: a client thread publishes prompt requests
(metadata → broker, bulk prompt → store) under a backpressure window, the
engine admits them into slots, decodes greedily, and streams *token deltas*
plus final completions back; a :class:`repro.serve.client.ServeClient`
assembles them and reports time-to-first-token.

The client's send window is bounded by completions (in-flight ≤ 2×slots)
and every blocking edge has a deadline, so a wedged engine or a full store
surfaces as a loud error instead of a silently deadlocked driver.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
        --requests 8 --slots 4 --max-new 12
"""
from __future__ import annotations

import argparse
import sys
import threading
import time

import jax
import numpy as np

from repro.configs import arch_names, get_config, get_smoke_config
from repro.core.store import Store
from repro.core.streaming import (
    QueuePublisher,
    QueueSubscriber,
    StreamConsumer,
    StreamProducer,
)
from repro.dist.sharding import materialize_params, sharding_tree
from repro.launch.compile_cache import use_compile_cache
from repro.models.api import build_model
from repro.serve.client import ServeClient
from repro.serve.engine import ServeEngine, serve_context


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m", choices=arch_names(True))
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-servable) "
                         "instead of the published widths")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--send-timeout", type=float, default=60.0,
                    help="client-side bound on one admission-window wait")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page granularity (paged decode needs "
                         "max-len % page-size == 0; else dense fallback)")
    ap.add_argument("--use-kernels", action="store_true",
                    help="dispatch attention through the Pallas kernel ops "
                         "(paged attention on the decode path)")
    ap.add_argument("--no-paged", dest="paged", action="store_false",
                    help="dense (L, B, max_len) KV layout instead of the "
                         "paged pool (the benchmark baseline)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decode: draft-proposed tokens per "
                         "slot per step (0 = off); emitted tokens stay "
                         "bit-identical to plain greedy decode")
    ap.add_argument("--draft-config", default=None, choices=arch_names(True),
                    help="config for the draft model (--spec-k > 0); "
                         "defaults to --arch (self-draft)")
    args = ap.parse_args(argv)

    use_compile_cache()
    pick_config = get_smoke_config if args.smoke else get_config
    cfg = pick_config(args.arch)
    # serve rules profile: kv_seq over model axis
    ctx = serve_context(
        cfg, use_kernels=args.use_kernels, page_size=args.page_size
    )
    model = build_model(ctx)
    with ctx.mesh:
        params = materialize_params(model.param_specs(), jax.random.PRNGKey(0))
        if ctx.mesh.size > 1:
            params = jax.device_put(
                params, sharding_tree(model.param_specs(), ctx.rules, ctx.mesh)
            )

    from repro.core.connectors import new_key

    ns = f"serve-demo-{new_key()}"  # unique per run: re-entrant in-process
    store = Store(f"{ns}-requests")
    producer = StreamProducer(QueuePublisher(ns), {"requests": store})
    consumer = StreamConsumer(QueueSubscriber("requests", ns), timeout=30.0)
    resp_store = Store(f"{ns}-responses")
    resp_producer = StreamProducer(QueuePublisher(ns), {"responses": resp_store})
    resp_consumer = StreamConsumer(QueueSubscriber("responses", ns), timeout=30.0)

    rng = np.random.default_rng(0)
    # Backpressure window: a send blocks once 2×slots requests are in
    # flight and is released per completion — the client can never run the
    # store/broker arbitrarily ahead of the engine (a blocked client used
    # to deadlock the driver: run() never returned, t.join() never ran).
    window = threading.Semaphore(2 * args.slots)
    client = ServeClient(resp_consumer, on_done=lambda *_: window.release())
    sent_at: dict[str, float] = {}
    client_err: list[BaseException] = []

    def send_requests():
        try:
            for i in range(args.requests):
                if not window.acquire(timeout=args.send_timeout):
                    raise TimeoutError(
                        f"admission window stalled for {args.send_timeout}s "
                        f"(engine wedged?)"
                    )
                prompt = rng.integers(
                    1, cfg.vocab, args.prompt_len
                ).astype(np.int32)
                sent_at[f"r{i}"] = time.perf_counter()
                producer.send(
                    "requests",
                    {"prompt": prompt},
                    metadata={"req_id": f"r{i}", "max_new_tokens": args.max_new},
                )
                producer.flush_topic("requests")
            producer.close_topic("requests")
        except BaseException as e:  # pragma: no cover - error path
            client_err.append(e)
            producer.close_topic("requests")

    def collect_responses():
        try:
            client.collect()  # until the engine closes the response topic
        except BaseException as e:  # pragma: no cover - error path
            client_err.append(e)

    sender = threading.Thread(target=send_requests, daemon=True)
    collector = threading.Thread(target=collect_responses, daemon=True)
    sender.start()
    collector.start()

    draft_model = draft_params = None
    if args.spec_k > 0:
        if args.draft_config is None or args.draft_config == args.arch:
            # self-draft: reuses the target's params (the degenerate case
            # that maximizes acceptance; a real deployment would pass a
            # smaller --draft-config)
            draft_model, draft_params = model, params
        else:
            dcfg = pick_config(args.draft_config)
            dctx = serve_context(
                dcfg, use_kernels=args.use_kernels, page_size=args.page_size
            )
            draft_model = build_model(dctx)
            with dctx.mesh:
                draft_params = materialize_params(
                    draft_model.param_specs(), jax.random.PRNGKey(1)
                )

    engine = ServeEngine(
        ctx, params, slots=args.slots, max_len=args.max_len,
        page_size=args.page_size, eos_id=-1, paged=args.paged,
        spec_k=args.spec_k, draft_model=draft_model, draft_params=draft_params,
    )
    t0 = time.perf_counter()
    completed = engine.run(consumer, resp_producer)
    wall = time.perf_counter() - t0
    # Bounded joins: the engine is done, so a still-blocked client is a bug
    # worth failing loudly on, not waiting forever for.
    sender.join(timeout=30)
    collector.join(timeout=30)
    if sender.is_alive() or collector.is_alive():
        raise RuntimeError("client threads did not drain after engine exit")
    if client_err:
        raise client_err[0]

    lat = [c["latency"] for c in completed.values()]
    ttfts = list(client.ttft_s(sent_at).values())
    spec_note = ""
    if args.spec_k > 0 and engine.metrics["spec_slot_steps"]:
        rate = (
            engine.metrics["spec_accepted_tokens"]
            / engine.metrics["spec_slot_steps"]
        )
        spec_note = f" accepted/slot-step {rate:.2f} (spec_k={args.spec_k});"
    print(
        f"[serve] {args.arch}{' (smoke)' if args.smoke else ''}: "
        f"{len(completed)}/{args.requests} requests, "
        f"{engine.metrics['tokens']} tokens in {wall:.1f}s "
        f"({engine.metrics['tokens']/wall:.1f} tok/s); "
        f"mean latency {np.mean(lat):.2f}s; "
        f"mean ttft {np.mean(ttfts):.3f}s (streamed deltas);{spec_note} "
        f"pages in use at exit: {engine.pages.pages_in_use()}"
    )
    streamed_ok = all(
        r.stream_tokens == r.result["tokens"]
        for r in client.results.values()
        if r.result is not None
    )
    ok = (
        len(completed) == args.requests
        and engine.pages.pages_in_use() == 0
        and (engine.draft_pages is None
             or engine.draft_pages.pages_in_use() == 0)
        and len(client.results) == args.requests
        and streamed_ok
    )
    engine.close()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
