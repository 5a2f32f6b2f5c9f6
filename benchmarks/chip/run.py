#!/usr/bin/env python3
"""On-chip serving benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<mix>.json``); per-layer metrics are ``metrics/<metric>.py``.
Everything is found by name; nothing here is particular to one cell.

A run:
1. checks the device: a TPU of a kind in ``peaks.json``, as many chips as
   the cell asks for, or it exits non-zero with no result;
2. set-up: makes the weights from the seed on the device in one jitted call,
   builds the server (one engine in this process), and warms every shape
   the mix can reach by sending each warm-up request alone through the
   served path.  ``setup_s`` runs from process start to here;
3. offers the mix's open-loop traffic and measures a window of
   ``--seconds``, from the client's side: every token's arrival time;
   with ``--trace 1`` the first ``TRACE_SECONDS`` of the window run under
   JAX's profiler and the run reports the per-layer metrics instead;
4. closes intake, waits for every request in flight to finish, reads peak
   device memory, frees the server, and checks the answers against the
   plain float32 reference (``reference.py``) on a sample drawn from the
   seed; each number compared is printed beside its limit, last on
   standard error and last in the result line;
5. prints one JSON line as the last line of standard output.

``--rehearse`` runs the same steps off the chip with the program's smoke
preset of the configuration, Pallas kernels in interpret mode and a capped
mix; it never prints a result and exits 3 when every step ran.

``--control`` is the control of the comparison, never one of the
benchmark's runs: the float8 reference's first choices take the served
tokens' place in ``max_logit_gap``, so a sound control prints
``correct: false``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import stats  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

TRACE_SECONDS = 10.0  # the traced part of a --trace 1 window
WARMUP_TIMEOUT_S = 900.0
DRAIN_TIMEOUT_S = 240.0
# rehearsal: the smoke preset at a size the CPU and interpret mode can hold
REHEARSE_ENGINE = {"slots": 4, "max_len": 256}
REHEARSE_CAPS = {"prompt_tokens": 128, "output_tokens": 24}
REHEARSE_LEAD_IN_S = 2.0


class Fail(Exception):
    """A run that cannot produce a result: exit non-zero, print none."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# The cell, found by name
# ---------------------------------------------------------------------------


class Cell:
    def __init__(self, name: str, rehearse: bool = False):
        bench_path = ROOT / "BENCHMARK.json"
        if not bench_path.exists():
            raise Fail(f"no {bench_path}")
        bench = json.loads(bench_path.read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise Fail(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.entry = cells[name]
        self.chips = self.entry["chips"]
        conf = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = json.loads((ROOT / conf["file"]).read_text())
        self.mix = json.loads((HERE / "traffic" / f"{self.entry['traffic']}.json").read_text())
        self.run_seconds = bench["run_seconds"]

        def mine(m):
            return name in m.get("workloads", cells)

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]
        self.engine = dict(self.config["engine"])
        self.rehearse = rehearse
        if rehearse:
            self.engine.update(REHEARSE_ENGINE)
            for key, cap in REHEARSE_CAPS.items():
                d = self.mix[key]
                if d["dist"] == "choice":
                    d["values"] = [min(v, cap) for v in d["values"]]
                d["max"] = min(d.get("max", cap), cap)
                d["min"] = min(d.get("min", 1), d["max"])
            self.mix["lead_in_s"] = min(self.mix["lead_in_s"], REHEARSE_LEAD_IN_S)

    def program_config(self):
        """The program's ``ArchConfig`` for this configuration, checked
        against the configuration file's published sizes."""
        from repro.configs import get_config, get_smoke_config

        prog = self.config["program"]
        if self.rehearse:
            return get_smoke_config(prog["arch"])
        cfg = get_config(prog["arch"]).with_(**prog["overrides"])
        c = self.config
        want = {
            "d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
            "n_heads": c["num_attention_heads"], "n_kv_heads": c["num_key_value_heads"],
            "n_layers": c["num_hidden_layers"], "vocab": c["vocab_size"],
            "tie_embeddings": c["tie_word_embeddings"], "rope_theta": c["rope_theta"],
            "padded_vocab": prog["padded_vocab"], "head_dim_": c["hidden_size"] // c["num_attention_heads"],
        }
        got = {k: getattr(cfg, k) for k in want}
        if got != want or cfg.family != "dense" or cfg.norm != "rmsnorm" or cfg.rotary_pct != 1.0:
            raise Fail(f"program config {prog['arch']} departs from the file: {got} vs {want}")
        return cfg

    def arch(self, cfg=None) -> dict:
        """The architecture as the reference, the weights and ``work`` read
        it: the file's keys as run (the smoke preset's when rehearsing)."""
        c = self.config
        a = {k: v for k, v in c.items() if not isinstance(v, (dict, list))}
        a["embed_std"] = c["weights"]["embed_std"]
        a["padded_vocab"] = c["program"]["padded_vocab"]
        if self.rehearse:
            a.update(
                hidden_size=cfg.d_model, intermediate_size=cfg.d_ff,
                num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
                num_hidden_layers=cfg.n_layers, vocab_size=cfg.vocab,
                padded_vocab=cfg.padded_vocab, tie_word_embeddings=cfg.tie_embeddings,
            )
        return a


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise Fail(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# Client-side record of every token
# ---------------------------------------------------------------------------


class Recorder:
    def __init__(self):
        self.cond = threading.Condition()
        self.times: dict[str, list[float]] = {}
        self.tokens: dict[str, list[int]] = {}
        self.done: dict[str, float] = {}
        self.result: dict[str, list[int]] = {}
        self.error: dict[str, str] = {}

    def on_delta(self, rid, token, index):
        t = time.perf_counter()
        with self.cond:
            self.times.setdefault(rid, []).append(t)
            self.tokens.setdefault(rid, []).append(int(token))
            self.cond.notify_all()

    def on_done(self, rid, rec):
        t = time.perf_counter()
        with self.cond:
            if rec.error is not None:
                self.error[rid] = rec.error
            else:
                self.done[rid] = t
                self.result[rid] = [int(x) for x in rec.result["tokens"]]
            self.cond.notify_all()

    def reset(self) -> None:
        with self.cond:
            for d in (self.times, self.tokens, self.done, self.result, self.error):
                d.clear()

    def wait(self, pred, timeout: float, what: str) -> None:
        with self.cond:
            if not self.cond.wait_for(pred, timeout):
                raise Fail(f"timed out after {timeout:g} s waiting for {what}")

    def finished(self, rid) -> bool:
        return rid in self.done or rid in self.error


# ---------------------------------------------------------------------------
# What a per-layer reader reads
# ---------------------------------------------------------------------------


class Window:
    """The traced window: device traces, counter deltas, and what the client
    saw, for ``metrics/<name>.py`` readers."""

    def __init__(self, *, traces, counters, arch, peak, engine, prefilled, decoded_ctx):
        self.traces = traces
        self.counters = counters
        self.arch = arch
        self.peak = peak
        self.engine = engine
        self.prefilled = prefilled  # prompt lengths admitted in the window
        self.decoded_ctx = decoded_ctx  # context length of each decoded token
        self.work = work

    @property
    def traced(self) -> bool:
        return bool(self.traces)

    def modules(self, match):
        return [e for t in self.traces for e in t.modules(match)]

    def kernel_ops(self, programs, match):
        """Operations matching ``match`` inside programs matching ``programs``."""
        return [e for t in self.traces for e in t.ops_in(programs, match)]

    def device_seconds(self, events) -> float:
        return sum(self.traces[0].seconds([e]) for e in events) if events else 0.0

    def mean_device_ms(self, match):
        """Mean device time of one run of the programs matching ``match``."""
        ev = self.modules(match)
        return 1e-6 * sum(e.end - e.start for e in ev) / len(ev) if ev else None

    def busy_s(self) -> float:
        return sum(t.busy_s() for t in self.traces) / len(self.traces)

    def window_s(self) -> float:
        return sum(t.window_s for t in self.traces) / len(self.traces)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


def use_cache() -> str:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program in
    it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    return path


def interpret_kernels() -> None:
    """Rehearsal: the model's kernel calls run the Pallas kernels in
    interpret mode."""
    import functools

    from repro.kernels import ops

    ops.flash_attention = functools.partial(ops.flash_attention, impl="interpret")
    ops.paged_attention = functools.partial(ops.paged_attention, impl="interpret")


class HostWatch:
    """Host stalls while traffic runs: a thread that asks to wake every
    ``TICK_S`` and records how late it wakes, and the garbage collector's
    pauses.  A late tick means the whole process was held (the GIL, a
    collection, or the host's scheduler), not one thread."""

    TICK_S = 0.02

    def __init__(self):
        self.ticks: list[tuple[float, float]] = []  # (lateness, when) of late ticks
        self.pauses: list[tuple[float, float, int]] = []  # (seconds, when, generation)
        self._gc_t0 = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._tick, name="host-watch", daemon=True)

    def _on_gc(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._gc_t0 = now
        else:
            self.pauses.append((now - self._gc_t0, now, info["generation"]))

    def _tick(self):
        due = time.perf_counter() + self.TICK_S
        while not self._stop.is_set():
            time.sleep(max(0.0, due - time.perf_counter()))  # proxylint: disable=no-sleep-poll
            now = time.perf_counter()
            if now - due > 0.005:
                self.ticks.append((now - due, now))
            due = now + self.TICK_S

    def start(self):
        gc.callbacks.append(self._on_gc)
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)

    def summary(self, t_open: float) -> str:
        def worst(xs):
            if not xs:
                return "none"
            x = max(xs)
            return f"max {1e3 * x[0]:.3f} ms at {x[1] - t_open:+.3f} s"

        return (f"host: {len(self.ticks)} late ticks, {worst(self.ticks)}; "
                f"{len(self.pauses)} collections, {worst(self.pauses)}")


class Run:
    def __init__(self, args, fault=None):
        self.args = args
        self.cell = Cell(args.workload, rehearse=args.rehearse)
        self.fault = fault  # self-checks: breaks the served path under the run
        self.rec = Recorder()
        self.sent: dict[str, float] = {}  # req_id -> due time (perf_counter)
        self.order: list[tuple[str, int]] = []  # (req_id, prompt length), send order
        self.reqs: dict[str, traffic.Req] = {}
        self.tmp = tempfile.mkdtemp(prefix="bench-")
        self.tag = "r"  # request ids of the traffic
        self.server = None

    # -- device ---------------------------------------------------------------
    def device_one_chip(self):
        import jax

        devs = jax.devices()
        dev = devs[0]
        if not self.args.rehearse:
            if dev.platform != "tpu":
                raise Fail(f"no TPU: JAX found {dev.platform}")
            if len(devs) < self.cell.chips:
                raise Fail(f"{len(devs)} chips, the cell asks for {self.cell.chips}")
            self.peak = peaks_for(dev.device_kind)
        else:
            self.peak = peaks_for("TPU v5 lite")
        self.dev = dev
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devs)}

    # -- set-up -----------------------------------------------------------------
    def build(self):
        import jax

        from servers import EngineServer
        import weights

        cfg = self.cfg = self.cell.program_config()
        self.arch = self.cell.arch(cfg)
        if self.args.rehearse:
            interpret_kernels()
        self.device_one_chip()
        params = weights.make(self.arch, self.args.seed, self.dev)
        self._check_layout(params)
        jax.block_until_ready(params)
        self.server = EngineServer(cfg, self.cell.engine, params,
                                   on_delta=self.rec.on_delta, on_done=self.rec.on_done)
        del params
        if self.fault is not None:
            self.fault(self.server)

    def _check_layout(self, params):
        import jax

        from repro.dist.sharding import ParamSpec
        from repro.models.api import build_model
        from repro.serve.engine import serve_context

        specs = build_model(serve_context(self.cfg)).param_specs()
        want = jax.tree.map(lambda s: (tuple(s.shape), str(jax.numpy.dtype(s.dtype))), specs,
                            is_leaf=lambda x: isinstance(x, ParamSpec))
        got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
        if want != got:
            raise Fail(f"the program's parameter layout departs from weights.py: {want} vs {got}")

    def warm(self):
        """Each warm-up request alone, through the served path."""
        reqs = traffic.warmup(self.cell.mix, self.arch["vocab_size"],
                              self.cell.engine["max_len"], self.cell.engine["page_size"])
        for r in reqs:
            self._send(r)
            self.rec.wait(lambda r=r: self.rec.finished(r.rid), WARMUP_TIMEOUT_S,
                          f"warm-up {r.rid}")

    def count_compiles(self) -> list[int]:
        """Counts the backend compilations that start inside the window."""
        import jax

        n = [0]

        def on_event(event, duration, **_):
            now = time.perf_counter()
            if "backend_compile" in event and self.t_open <= now - duration < self.t_close:
                n[0] += 1

        self.t_open = self.t_close = math.inf
        jax.monitoring.register_event_duration_secs_listener(on_event)
        return n

    def _send(self, r: traffic.Req, due: float | None = None):
        self.reqs[r.rid] = r
        self.order.append((r.rid, len(r.prompt)))
        self.sent[r.rid] = due if due is not None else time.perf_counter()
        self.server.send(r)

    # -- traffic ----------------------------------------------------------------
    def offer(self):
        """Offer the mix and measure the window."""
        mix, seconds = self.cell.mix, self.args.seconds
        sched = traffic.open_loop(mix, seconds, self.args.seed, self.arch["vocab_size"], self.tag)
        self.wake_late: list[tuple[float, float]] = []  # (how late the generator woke, due)
        self.send_s: list[float] = []  # how long each send took
        t0 = time.perf_counter()
        self.t_open, self.t_close = t0 + mix["lead_in_s"], t0 + mix["lead_in_s"] + seconds

        def gen():
            for r in sched:
                due = t0 + r.due
                wait = due - time.perf_counter()
                if wait > 0:  # until the arrival is due: a schedule, not a poll
                    time.sleep(wait)  # proxylint: disable=no-sleep-poll
                woke = time.perf_counter()
                self._send(r, due)
                self.wake_late.append((woke - due, due))
                self.send_s.append(time.perf_counter() - woke)

        sender = threading.Thread(target=gen, name="generator", daemon=True)
        sender.start()
        self.window()
        sender.join()

    def window(self):
        """Wait out the window; with --trace 1, trace its first part."""
        self.counters = None
        self.trace_span = None
        now = time.perf_counter()
        if self.t_open > now:
            time.sleep(self.t_open - now)
        if self.args.trace:
            import trace

            span = min(self.args.seconds, TRACE_SECONDS)
            c0 = self.server.counters()
            self.trace_dir = os.path.join(self.tmp, "trace")
            lo = time.perf_counter()
            with trace.capture(self.trace_dir):
                time.sleep(span)
            hi = time.perf_counter()
            c1 = self.server.counters()
            self.counters = {k: c1[k] - c0[k] for k in c1}
            self.counters["prefills_at"] = (c0["prefills"], c1["prefills"])
            self.trace_span = (lo, hi)
        now = time.perf_counter()
        if self.t_close > now:
            time.sleep(self.t_close - now)

    # -- end-to-end metrics -------------------------------------------------------
    def in_window(self) -> list[str]:
        """Requests due in the window."""
        return [r for r, t in self.sent.items() if self.t_open <= t < self.t_close]

    def end_to_end(self) -> dict:
        lo, hi = self.t_open, self.t_close
        times = self.rec.times
        out = {}
        n_tok = sum(1 for ts in times.values() for t in ts if lo <= t < hi)
        gaps = [b - a for ts in times.values() for a, b in zip(ts, ts[1:]) if lo <= a and b < hi]
        due = self.in_window()
        ttft = [times[r][0] - self.sent[r] if r in times else math.inf for r in due]
        values = {
            "output_tok_s": n_tok / (hi - lo),
            "itl_p99_ms": 1e3 * stats.percentile(gaps, 99) if gaps else None,
            "setup_s": self.setup_s,
        }
        log(f"window {hi - lo:.3f} s: {n_tok} tokens, {len(due)} requests due, "
            f"{len(gaps)} gaps ({stats.beyond(len(gaps), 99)} beyond p99)")
        if ttft:  # no bound holds it: logged
            log(f"ttft p50 {1e3 * stats.percentile(ttft, 50):.3f} ms "
                f"({stats.beyond(len(ttft), 50)} beyond), "
                f"p90 {1e3 * stats.percentile(ttft, 90):.3f} ms ({stats.beyond(len(ttft), 90)} beyond)")
        for m in self.cell.end_to_end:
            v = values.get(m["name"])
            if v is None or not math.isfinite(v):
                raise Fail(f"{m['name']} cannot be read in this run: {v}")
            out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out

    # -- per-layer metrics ----------------------------------------------------------
    def per_layer(self) -> tuple[dict, dict]:
        import trace

        try:
            t = trace.Trace.load(self.trace_dir)
        except RuntimeError as e:
            raise Fail(str(e)) from e
        lo, hi = self.trace_span
        a, b = self.counters["prefills_at"]
        prefilled = [n for _, n in self.order[a:b]]
        decoded = []
        for rid, ts in self.rec.times.items():
            plen = len(self.reqs[rid].prompt)
            decoded += [plen + i for i, t_ in enumerate(ts) if i >= 1 and lo <= t_ < hi]
        w = Window(traces=[t], counters=self.counters, arch=self.arch, peak=self.peak,
                   engine=self.cell.engine, prefilled=prefilled, decoded_ctx=decoded)
        log(t.describe())
        out = {}
        for m in self.cell.per_layer:
            v = load_reader(m["name"]).read(w)
            if v is None:
                if "workloads" not in m:
                    continue
                if self.args.rehearse:  # no kernel or device names off the chip
                    log(f"per-layer metric {m['name']} found nothing to read")
                    continue
                raise Fail(f"per-layer metric {m['name']} found nothing to read")
            out[m["name"]] = {"value": v, "unit": m["unit"]}
        self.device["busy_s"] = w.busy_s()
        self.device["window_s"] = w.window_s()
        return out, t.breakdown()

    # -- correctness ------------------------------------------------------------------
    def sample(self, finished: list[str]) -> list[str]:
        """A sample drawn from the seed, with the longest answer in it."""
        k = self.cell.config["check"]["sample_requests"]
        longest = max(finished, key=lambda r: (len(self.rec.result[r]), len(self.reqs[r].prompt), r))
        rest = sorted(r for r in finished if r != longest)
        return [longest] + random.Random(self.args.seed).sample(rest, min(k - 1, len(rest)))

    def checks(self, due: list[str]) -> tuple[dict, list[str]]:
        rec = self.rec
        unanswered = [r for r in due if r not in rec.done]
        finished = [r for r in self.sent if r in rec.done and r not in self.warmups]
        stream_bad = [r for r in finished if rec.tokens.get(r) != rec.result[r]]
        length_bad = [r for r in finished if len(rec.result[r]) != self.reqs[r].max_new]
        return {
            "unanswered_in_window": {"value": len(unanswered), "limit": 0},
            "stream_differs_from_completion": {"value": len(stream_bad), "limit": 0},
            "wrong_answer_length": {"value": len(length_bad), "limit": 0},
        }, finished

    def compare(self, sample: list[str]) -> dict:
        """The widest gap of the served tokens over the sampled requests;
        with ``--control``, also of the float8 reference's first choices and
        of the served tokens against the reference at the published RMSNorm
        eps."""
        import reference
        import weights

        arch = self.arch
        w = weights.make(arch, self.args.seed)
        pad_to = self.cell.engine["max_len"]
        out_pad = max(traffic.support(self.cell.mix["output_tokens"]))
        control = self.args.control
        published = dict(arch, rms_norm_eps=self.cell.config["reduced"]["rms_norm_eps"]["published"])
        worst = {"served": 0.0, "control": 0.0, "published_eps": 0.0}
        n = 0
        for r in sample:
            prompt, served = self.reqs[r].prompt, self.rec.result[r]
            g = reference.gaps(w, arch, prompt, served, pad_to=pad_to, out_pad=out_pad,
                               control=control)
            if control:
                g["published_eps"] = reference.gaps(w, published, prompt, served, pad_to=pad_to,
                                                    out_pad=out_pad)["served"]
            for k, v in g.items():
                worst[k] = max(worst[k], float(v.max()))
            n += len(served)
        return {**worst, "tokens": n}

    # -- all of it -----------------------------------------------------------------------
    def execute(self) -> dict:
        try:
            return self._execute()
        except BaseException:
            if self.server is not None:
                self.server.stop()
            raise

    def _execute(self) -> dict:
        self.build()
        self.warm()
        self.warmups = set(self.sent)
        gc.collect()
        gc.freeze()  # what set-up made is never collected again: shorter pauses after
        self.setup_s = time.perf_counter() - T_START
        log(f"set-up {self.setup_s:.3f} s")
        compiles = self.count_compiles()
        watch = HostWatch()
        watch.start()
        try:
            self.offer()
        finally:
            watch.stop()
        log(f"compilations inside the window: {compiles[0]}")
        log(watch.summary(self.t_open))
        late, when = max(self.wake_late)
        log(f"generator: woke late by max {1e3 * late:.3f} ms at {when - self.t_open:+.3f} s; "
            f"send max {1e3 * max(self.send_s):.3f} ms")
        self.server.close_intake()
        self.server.join(DRAIN_TIMEOUT_S)
        due = self.in_window()
        checks, finished = self.checks(due)
        self.device["memory_peak_bytes"] = (self.dev.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)
        checks["pages_in_use_after_drain"] = {"value": self.server.pages_in_use(), "limit": 0}
        self.server.stop()
        self.server = None
        gc.collect()
        if self.args.trace:
            metrics, breakdown = self.per_layer()
        else:
            metrics, breakdown = self.end_to_end(), None
        sample = self.sample(finished)
        t0 = time.perf_counter()
        gap = self.gap = self.compare(sample)
        log(f"reference over {len(sample)} requests, {gap['tokens']} tokens: "
            f"{time.perf_counter() - t0:.3f} s")
        limit = self.cell.config["check"]["rehearse_max_logit_gap" if self.args.rehearse
                                          else "max_logit_gap"]
        if self.args.control:  # the float8 picks in the served tokens' place
            log(f"control: served tokens' widest gap {gap['served']!r}; at the published "
                f"RMSNorm eps {gap['published_eps']!r}")
            checks["max_logit_gap"] = {"value": gap["control"], "limit": limit}
        else:
            checks["max_logit_gap"] = {"value": gap["served"], "limit": limit}
        correct = gap["tokens"] > 0 and all(c["value"] <= c["limit"] for c in checks.values())
        out = {
            "correct": correct,
            "attempted": len(due),
            "failed": checks["unanswered_in_window"]["value"],
            "metrics": metrics,
            "device": self.device,
        }
        if breakdown is not None:
            out["breakdown"] = breakdown
        out["checks"] = checks
        for name, c in checks.items():
            print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr, flush=True)
        return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="smoke preset, interpret-mode kernels, any backend; prints no result")
    ap.add_argument("--control", action="store_true",
                    help="the float8 reference's picks in the served tokens' place")
    return ap.parse_args(argv)


def prepare(args) -> None:
    if not (ROOT / "src" / "repro").is_dir():
        raise Fail(f"no {ROOT / 'src' / 'repro'}: run from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    use_cache()
    import jax

    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        if not args.workload:
            raise Fail("--workload is required")
        prepare(args)
        if args.seconds is None:
            args.seconds = Cell(args.workload).run_seconds
        run = Run(args)
        try:
            out = run.execute()
        finally:
            shutil.rmtree(run.tmp, ignore_errors=True)
    except Fail as e:
        log(f"FAIL {e}")
        return 1
    if args.rehearse:
        log(f"rehearsal: every step ran (correct={out['correct']}); no result is printed off the chip")
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
