"""Serve fleet launcher: N engine processes behind one failover router.

Topology (one driver process + one store server + N engines)::

    client ──requests──▶ Router ──requests-eI──▶ ServeEngine (proc eI)
       ▲                   │  ▲                        │
       └──responses◀───────┘  └─lease watch    responses-eI / load-eI

- Bulk payloads (prompts, completions) live on a TCP ``StoreServer``;
  the FileLog broker carries only metadata events, so the router stays a
  metadata-only hop (it never resolves a proxy).
- Engines register under a :class:`~repro.dist.lease.LeaseService` on the
  control namespace and renew at ``ttl/4``; the router redispatches a dead
  engine's in-flight requests to survivors (see ``repro.serve.router``).
- Prompts are published with ``evict_on_resolve=False`` and completions
  are committed via ``send_committed`` at ``done-{req_id}``, so a request
  re-served after a SIGKILL resolves the same prompt bytes and twin
  completions share one payload cell — no request is lost or double-
  delivered.

Subcommands::

    python -m repro.launch.fleet engine --name e0 --addr H:P --dir LOG \\
        --prefix fleet-x --toy ...        # one fleet engine (subprocess)
    python -m repro.launch.fleet demo --engines 2 --requests 8   # local demo

On a host with several TPU chips, ``Fleet(..., pin_chips=True)`` gives
engine ``i`` chip ``i`` alone through libtpu's per-process visibility
settings (``TPU_VISIBLE_CHIPS`` with one-chip process bounds), so N engines
are N one-chip replicas.  Each engine reports the device it holds in its
READY line (``EngineProc.device``); the process that holds the ``Fleet``
never initializes a JAX backend, so it holds no chip.
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time

from repro.core.connectors import new_key
from repro.core.connectors_net import StoreServer, StoreServerConnector
from repro.core.store import Store
from repro.core.streaming import (
    FileLogPublisher,
    FileLogSubscriber,
    StreamConsumer,
    StreamProducer,
)

READY_LINE = "FLEET ENGINE READY"
LEASE_PREFIX = "fleet"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def pin_to_chip(env: dict, chip: int) -> dict:
    """Make ``chip`` the only TPU chip a process started with ``env`` sees:
    a one-chip process slice, which libtpu lets load beside other such
    processes on the same host."""
    port = _free_port()
    env.update(
        TPU_VISIBLE_CHIPS=str(chip),
        TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
        TPU_PROCESS_BOUNDS="1,1,1",
        TPU_PROCESS_PORT=str(port),
        TPU_PROCESS_ADDRESSES=f"localhost:{port}",
    )
    return env


def _env_with_src() -> dict:
    """Subprocess env whose PYTHONPATH reaches this ``repro`` package."""
    import repro

    env = dict(os.environ)
    # namespace-package tolerant: __file__ may be None, __path__ is not
    pkg_dir = (
        os.path.dirname(os.path.abspath(repro.__file__))
        if getattr(repro, "__file__", None)
        else os.path.abspath(next(iter(repro.__path__)))
    )
    src = os.path.dirname(pkg_dir)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return env


# ---------------------------------------------------------------------------
# Engine subprocess entry point
# ---------------------------------------------------------------------------


def _engine_main(args) -> int:
    """One fleet engine: lease heartbeat + serve loop over the fleet topics.

    Prints ``FLEET ENGINE READY <name> <device json>`` (flushed) once the
    lease is held and the initial load cell is published, so the spawner
    can scrape it and learn which device the engine holds.
    """
    import jax

    from repro.configs import get_config, get_smoke_config
    from repro.dist.lease import LeaseLost, LeaseService
    from repro.launch.compile_cache import use_compile_cache
    from repro.serve.engine import ServeEngine, serve_context

    use_compile_cache()
    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    ctx = serve_context(
        cfg, use_kernels=args.use_kernels, page_size=args.page_size
    )
    if args.toy:
        from repro.serve.toy import CountingModel

        model, params = CountingModel(cfg), {}
    else:
        from repro.dist.sharding import materialize_params
        from repro.models.api import build_model

        model = build_model(ctx)
        with ctx.mesh:
            params = materialize_params(
                model.param_specs(), jax.random.PRNGKey(0)
            )
    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "id": dev.id,
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
    }

    name = args.name
    ctl_store = Store(
        f"{args.prefix}-ctl",
        StoreServerConnector(args.addr, namespace="ctl"),
        register=False,
    )
    resp_store = Store(
        f"{args.prefix}-resp",
        StoreServerConnector(args.addr, namespace="resp"),
        register=False,
    )

    engine = ServeEngine(
        ctx,
        params,
        model=model,
        slots=args.slots,
        max_len=args.max_len,
        page_size=args.page_size,
        eos_id=args.eos_id,
        # fleet hooks: least-loaded routing + exactly-once completions
        on_load_change=lambda pages: ctl_store.put(
            pages, key=f"load-{name}"
        ),
        done_commit_prefix="done-",
    )

    lease = LeaseService(ctl_store, ttl=args.ttl, prefix=LEASE_PREFIX)
    gen = [lease.register(name)]
    ctl_store.put(engine.pages.pages_available(), key=f"load-{name}")
    print(f"{READY_LINE} {name} {json.dumps(device)}", flush=True)

    stop = threading.Event()
    beat_errors = [0]

    def heartbeat():
        while not stop.wait(args.ttl / 4):
            try:
                lease.renew(name, gen[0])
            except LeaseLost:
                # fenced out: a newer incarnation owns this name — this
                # process must stop serving rather than split-brain
                os._exit(17)
            except TimeoutError:  # LeaseExpired: dead until re-registered
                try:
                    gen[0] = lease.register(name)
                except Exception:
                    beat_errors[0] += 1
            except Exception:
                beat_errors[0] += 1  # transient channel error: keep beating

    hb = threading.Thread(target=heartbeat, name="fleet-heartbeat", daemon=True)
    hb.start()

    if args.hold_key:
        # chaos hook: hold BEFORE the serve loop — the engine is a lease-
        # holding, load-publishing member that never admits anything
        ctl_store.wait_for(args.hold_key, timeout=600.0)

    consumer = StreamConsumer(
        FileLogSubscriber(f"requests-{name}", args.dir), timeout=120.0
    )
    producer = StreamProducer(FileLogPublisher(args.dir), {"*": resp_store})
    try:
        engine.run(consumer, producer, response_topic=f"responses-{name}")
    finally:
        stop.set()
        # completion bulks stay resident for lagging clients (their
        # one-shot resolves reclaim them); prompts are reclaimed here
        engine.close(reclaim_responses=False)
    return 0


# ---------------------------------------------------------------------------
# Driver-side process handle + fleet harness
# ---------------------------------------------------------------------------


class EngineProc:
    """Spawn/scrape/kill handle for one ``fleet engine`` subprocess."""

    def __init__(
        self,
        name: str,
        addr: str,
        logdir: str,
        prefix: str,
        *,
        arch: str = "smollm-135m",
        toy: bool = True,
        smoke: bool = True,
        use_kernels: bool = False,
        slots: int = 2,
        max_len: int = 32,
        page_size: int = 4,
        ttl: float = 3.0,
        hold_key: str | None = None,
        chip: int | None = None,
    ):
        self.name = name
        self.device: dict | None = None  # from the READY line
        cmd = [
            sys.executable, "-m", "repro.launch.fleet", "engine",
            "--name", name, "--addr", addr, "--dir", logdir,
            "--prefix", prefix, "--arch", arch,
            "--slots", str(slots), "--max-len", str(max_len),
            "--page-size", str(page_size), "--ttl", str(ttl),
        ]
        for flag, on in (("--toy", toy), ("--smoke", smoke),
                         ("--use-kernels", use_kernels)):
            if on:
                cmd.append(flag)
        if hold_key:
            cmd += ["--hold-key", hold_key]
        env = _env_with_src()
        if chip is not None:
            pin_to_chip(env, chip)
        self._errpath = os.path.join(logdir, f"{name}.stderr")
        self._errfile = open(self._errpath, "wb")
        self.proc = subprocess.Popen(
            cmd,
            env=env,
            stdout=subprocess.PIPE,
            stderr=self._errfile,
        )

    def wait_ready(self) -> None:
        """Block until the READY line (EOF ⇒ startup crash, stderr shown)."""
        while True:
            line = self.proc.stdout.readline()
            if not line:
                err = ""
                try:
                    with open(self._errpath, "rb") as f:
                        err = f.read().decode(errors="replace")[-4000:]
                except OSError:
                    pass
                raise RuntimeError(
                    f"fleet engine {self.name} exited before READY "
                    f"(rc={self.proc.poll()}):\n{err}"
                )
            text = line.decode(errors="replace")
            if text.startswith(READY_LINE):
                self.device = json.loads(text[len(READY_LINE):].split(None, 1)[1])
                break
        # drain further stdout so the pipe can never fill and block the
        # engine's prints
        threading.Thread(
            target=lambda: [None for _ in iter(self.proc.stdout.readline, b"")],
            name=f"drain-{self.name}",
            daemon=True,
        ).start()

    def kill(self) -> None:
        """SIGKILL — the chaos primitive: no cleanup, no lease release."""
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(timeout=30)
        self._errfile.close()

    def alive(self) -> bool:
        return self.proc.poll() is None

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        self._errfile.close()


class Fleet:
    """An N-engine serve fleet in one object (driver-process side).

    Owns the store server, the FileLog directory, the engine subprocesses,
    the router, and the client-side producer/consumer pair.  Tests drive
    chaos through :meth:`kill_engine` / router hooks; the benchmark drives
    throughput through :func:`run_fleet`.
    """

    def __init__(
        self,
        n_engines: int,
        *,
        arch: str = "smollm-135m",
        toy: bool = True,
        smoke: bool = True,
        use_kernels: bool = False,
        pin_chips: bool = False,
        slots: int = 2,
        max_len: int = 32,
        page_size: int = 4,
        ttl: float = 3.0,
        tick: float = 0.05,
        hold: tuple = (),
        logdir: str | None = None,
        consumer_timeout: float = 300.0,
        on_done=None,
    ):
        from repro.configs import get_config, get_smoke_config
        from repro.dist.lease import LeaseService
        from repro.serve.client import ServeClient
        from repro.serve.router import Router

        self.cfg = (get_smoke_config if smoke else get_config)(arch)
        self.names = [f"e{i}" for i in range(n_engines)]
        self.logdir = logdir or tempfile.mkdtemp(prefix="fleet-log-")
        self.prefix = f"fleet-{new_key()}"
        self.server = StoreServer().start()
        addr = self.server.address
        self.ctl_store = Store(
            f"{self.prefix}-ctl",
            StoreServerConnector(addr, namespace="ctl"),
            register=False,
        )
        req_store = Store(
            f"{self.prefix}-req",
            StoreServerConnector(addr, namespace="req"),
            register=False,
        )
        self.procs = {
            name: EngineProc(
                name, addr, self.logdir, self.prefix,
                arch=arch, toy=toy, smoke=smoke, use_kernels=use_kernels,
                slots=slots, max_len=max_len, page_size=page_size, ttl=ttl,
                hold_key=f"hold-{name}" if name in hold else None,
                chip=i if pin_chips else None,
            )
            for i, name in enumerate(self.names)
        }
        for proc in self.procs.values():
            proc.wait_ready()
        self.lease = LeaseService(self.ctl_store, ttl=ttl, prefix=LEASE_PREFIX)
        self.router = Router(
            self.names,
            subscriber=FileLogSubscriber("requests", self.logdir),
            publisher=FileLogPublisher(self.logdir),
            make_engine_subscriber=lambda n: FileLogSubscriber(
                f"responses-{n}", self.logdir
            ),
            lease=self.lease,
            control_store=self.ctl_store,
            tick=tick,
        ).start()
        # persistent prompt bulks: a redispatched request's survivor engine
        # must be able to re-resolve the same key
        self.producer = StreamProducer(
            FileLogPublisher(self.logdir),
            {"requests": req_store},
            evict_on_resolve=False,
        )
        self.client = ServeClient(
            StreamConsumer(
                FileLogSubscriber("responses", self.logdir),
                timeout=consumer_timeout,
            ),
            on_done=on_done,
        )
        self.sent_at: dict[str, float] = {}

    # -- client side ---------------------------------------------------------
    def send(self, req_id: str, prompt, max_new: int) -> None:
        self.sent_at[req_id] = time.perf_counter()
        self.producer.send(
            "requests",
            {"prompt": prompt},
            metadata={"req_id": req_id, "max_new_tokens": max_new},
        )
        self.producer.flush_topic("requests")

    def close_intake(self) -> None:
        self.producer.close_topic("requests")

    # -- chaos ---------------------------------------------------------------
    def kill_engine(self, name: str) -> None:
        self.procs[name].kill()

    def release_hold(self, name: str) -> None:
        self.ctl_store.put(True, key=f"hold-{name}")

    # -- teardown ------------------------------------------------------------
    def stop(self) -> None:
        self.router.close()
        for proc in self.procs.values():
            proc.stop()
        self.server.stop()


def run_fleet(
    n_engines: int,
    *,
    requests: int,
    max_new: int = 16,
    prompt_len: int = 5,
    slots: int = 2,
    max_len: int = 64,
    page_size: int = 4,
    ttl: float = 5.0,
    warmup: int | None = None,
    seed: int = 0,
) -> dict:
    """One measured fleet run: warmup round, then a timed request batch.

    Returns aggregate tokens/s over the measured batch, the per-request
    TTFT distribution, the final per-engine assignment counts, and the
    router metrics — the numbers the ``fleet_scaling`` benchmark gates.
    """
    import numpy as np

    fleet = Fleet(
        n_engines,
        slots=slots,
        max_len=max_len,
        page_size=page_size,
        ttl=ttl,
    )
    rng = np.random.default_rng(seed)

    def prompt():
        return rng.integers(1, fleet.cfg.vocab, prompt_len).astype(np.int32)

    try:
        n_warm = n_engines * slots if warmup is None else warmup
        for i in range(n_warm):
            fleet.send(f"w{i}", prompt(), max_new)
        if n_warm:
            fleet.client.collect(n_warm, deadline=300.0)
        t0 = time.perf_counter()
        for i in range(requests):
            fleet.send(f"r{i}", prompt(), max_new)
        fleet.close_intake()
        fleet.client.collect(deadline=300.0)  # until the router closes
        measured = {
            rid: rec
            for rid, rec in fleet.client.results.items()
            if rid.startswith("r") and rec.result is not None
        }
        if len(measured) != requests:
            raise RuntimeError(
                f"fleet run incomplete: {len(measured)}/{requests} measured "
                f"requests finished (router: {fleet.router.metrics})"
            )
        wall = max(rec.done_at for rec in measured.values()) - t0
        tokens = sum(len(rec.result["tokens"]) for rec in measured.values())
        ttfts = sorted(
            rec.first_delta_at - fleet.sent_at[rid]
            for rid, rec in measured.items()
            if rec.first_delta_at is not None
        )
        assignment = fleet.router.snapshot()
        per_engine: dict[str, int] = {n: 0 for n in fleet.names}
        for rid in measured:
            per_engine[assignment[rid][0]] += 1
        return {
            "n_engines": n_engines,
            "requests": requests,
            "wall_s": wall,
            "tokens": tokens,
            "tokens_per_s": tokens / wall,
            "p50_ttft_s": ttfts[len(ttfts) // 2] if ttfts else 0.0,
            "p99_ttft_s": ttfts[min(len(ttfts) - 1, int(0.99 * len(ttfts)))]
            if ttfts
            else 0.0,
            "per_engine": per_engine,
            "router_metrics": dict(fleet.router.metrics),
        }
    finally:
        fleet.stop()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    eng = sub.add_parser("engine", help="run one fleet engine (subprocess)")
    eng.add_argument("--name", required=True)
    eng.add_argument("--addr", required=True, help="store server host:port")
    eng.add_argument("--dir", required=True, help="FileLog broker directory")
    eng.add_argument("--prefix", required=True, help="run-unique store prefix")
    eng.add_argument("--arch", default="smollm-135m")
    eng.add_argument("--toy", action="store_true",
                     help="CountingModel instead of the real arch")
    eng.add_argument("--smoke", action="store_true",
                     help="the reduced same-family config instead of the "
                          "published widths")
    eng.add_argument("--use-kernels", action="store_true",
                     help="attention through the Pallas kernel ops")
    eng.add_argument("--slots", type=int, default=2)
    eng.add_argument("--max-len", type=int, default=32)
    eng.add_argument("--page-size", type=int, default=4)
    eng.add_argument("--eos-id", type=int, default=-1)
    eng.add_argument("--ttl", type=float, default=3.0)
    eng.add_argument("--hold-key", default=None,
                     help="wait on this control-store key before serving "
                     "(chaos hook: lease-live but never admitting)")

    demo = sub.add_parser("demo", help="run a local N-engine fleet demo")
    demo.add_argument("--engines", type=int, default=2)
    demo.add_argument("--requests", type=int, default=8)
    demo.add_argument("--max-new", type=int, default=16)
    demo.add_argument("--slots", type=int, default=2)

    args = ap.parse_args(argv)
    if args.cmd == "engine":
        return _engine_main(args)
    stats = run_fleet(
        args.engines,
        requests=args.requests,
        max_new=args.max_new,
        slots=args.slots,
    )
    print(
        f"[fleet] {stats['n_engines']} engines: {stats['requests']} requests, "
        f"{stats['tokens']} tokens in {stats['wall_s']:.2f}s "
        f"({stats['tokens_per_s']:.1f} tok/s); "
        f"p99 ttft {stats['p99_ttft_s'] * 1e3:.1f}ms; "
        f"per-engine {stats['per_engine']}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
