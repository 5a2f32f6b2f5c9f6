"""Device traces: capture a window with JAX's profiler, and reduce the
``.xplane.pb`` it writes to events on one clock.

Device planes are those named ``/device:...``; on each, events of a line
whose name holds ``Ops`` are operations and of one whose name holds
``Modules`` are whole programs.  Host threads are the lines of ``/host:CPU``.
An event is matched by its name and its string-valued stats (``hlo_op``,
``hlo_module``, ``long_name``, ...), as the trace prints them.

A trace recorded on the CPU (the self-checks) has no device plane: there the
events that carry an ``hlo_op`` stat are the operations, and each program run
(ops sharing a ``run_id``) spans one module event named by its
``hlo_module``.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
from dataclasses import dataclass

WINDOW = "bench.window"


@dataclass
class Event:
    name: str
    start: int  # ns on the trace's clock
    end: int
    text: str  # name and string stats, for matching
    stats: dict

    def clip(self, lo: int, hi: int) -> int:
        return max(0, min(self.end, hi) - max(self.start, lo))


def options():
    """The profiler's settings: device and host events, no Python tracer
    (it records every Python call and slows the host the window measures)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


@contextlib.contextmanager
def capture(logdir: str):
    """Trace the body; the window is the ``bench.window`` host span."""
    import jax

    jax.profiler.start_trace(logdir, profiler_options=options())
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def _events(line) -> list[Event]:
    out = []
    for e in line.events:
        stats = dict(e.stats)
        texts = [e.name] + [f"{k}={v}" for k, v in stats.items() if isinstance(v, str)]
        start = int(e.start_ns)
        out.append(Event(e.name, start, start + int(e.duration_ns), " ".join(texts), stats))
    return out


def short_name(name: str) -> str:
    """``%while.46 while`` for a TPU op event, whose name is the whole HLO
    instruction (``%while.46 = (shapes) while(operands), ...``)."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    op = re.search(r"(?<![\w.-])([a-z][a-z0-9-]*)\(", rest)
    return f"{head} {op.group(1)}" if op else head


@dataclass
class DeviceTrace:
    ops: list[Event]
    modules: list[Event]


def _cpu_device(host: list[Event]) -> list[DeviceTrace]:
    ops = [e for e in host if "hlo_op" in e.stats]
    runs: dict = {}
    for e in ops:
        runs.setdefault((e.stats.get("hlo_module"), e.stats.get("run_id")), []).append(e)
    mods = [
        Event(m, min(e.start for e in es), max(e.end for e in es), f"{m} hlo_module={m}", {})
        for (m, _), es in runs.items()
    ]
    return [DeviceTrace(ops, mods)] if ops else []


class Trace:
    """One process's trace, reduced to its window, device events and host
    events."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        self.devices: list[DeviceTrace] = []
        self.host: list[Event] = []
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                ops, mods = [], []
                for line in plane.lines:
                    if "Ops" in line.name:
                        ops += _events(line)
                    elif "Modules" in line.name:
                        mods += _events(line)
                if ops or mods:
                    self.devices.append(DeviceTrace(ops, mods))
            elif plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    self.host += _events(line)
        if not self.devices:
            self.devices = _cpu_device(self.host)
        wins = [e for e in self.host if e.name == WINDOW]
        if len(wins) != 1:
            raise RuntimeError(f"trace {path}: {len(wins)} '{WINDOW}' spans, want 1")
        self.lo, self.hi = wins[0].start, wins[0].end
        if not self.devices:
            seen = [(p.name, [(ln.name, len(list(ln.events))) for ln in p.lines]) for p in pd.planes]
            raise RuntimeError(f"trace {path}: no device plane with events; planes {seen}")

    @classmethod
    def load(cls, logdir: str) -> "Trace":
        paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
        if not paths:
            raise RuntimeError(f"no .xplane.pb under {logdir}")
        return cls(paths[-1])

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def _inside(self, events, match) -> list[Event]:
        return [
            e for e in events
            if e.end > self.lo and e.start < self.hi
            and any(m in e.text for m in match)
        ]

    def ops(self, match) -> list[Event]:
        """Device operations in the window matching any of ``match``."""
        return [e for d in self.devices for e in self._inside(d.ops, match)]

    def ops_in(self, programs, match) -> list[Event]:
        """Device operations in the window matching any of ``match`` that
        run inside a program matching any of ``programs`` (a kernel is known
        by the program that calls it: the trace names its custom call by
        its place in the HLO, not by the kernel)."""
        out = []
        for d in self.devices:
            spans = sorted((m.start, m.end) for m in self._inside(d.modules, programs))
            starts = [a for a, _ in spans]
            for e in self._inside(d.ops, match):
                i = bisect.bisect_right(starts, e.start) - 1
                if i >= 0 and e.end <= spans[i][1]:
                    out.append(e)
        return out

    def modules(self, match) -> list[Event]:
        """Whole programs run in the window matching any of ``match``."""
        return [e for d in self.devices for e in self._inside(d.modules, match)]

    def seconds(self, events) -> float:
        return sum(e.clip(self.lo, self.hi) for e in events) / 1e9

    def _busy(self, d: DeviceTrace) -> list[tuple[int, int]]:
        """Intervals in which a program ran (the ops line also holds async
        copies whose events span whole steps, so it overstates)."""
        spans = sorted(
            (max(e.start, self.lo), min(e.end, self.hi))
            for e in (d.modules or d.ops) if e.end > self.lo and e.start < self.hi
        )
        merged: list[list[int]] = []
        for a, b in spans:
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        return [(a, b) for a, b in merged]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        per = [sum(b - a for a, b in self._busy(d)) for d in self.devices]
        return sum(per) / len(per) / 1e9

    def describe(self, top: int = 12) -> str:
        """The names a reader can match: per device, the programs and the
        operations that took most time in the window."""
        out = [f"trace window {self.window_s:.6f} s, {len(self.devices)} device(s)"]
        for i, d in enumerate(self.devices):
            for kind, events in (("programs", d.modules), ("ops", d.ops)):
                tot: dict[str, list] = {}
                for e in events:
                    t = e.clip(self.lo, self.hi)
                    if t:
                        s = tot.setdefault(e.name, [0, 0, e.text[:160]])
                        s[0] += t
                        s[1] += 1
                best = sorted(tot.items(), key=lambda kv: -kv[1][0])[:top]
                out.append(f"  device {i} {kind}: " + "; ".join(
                    f"{n[:100]} x{c} {t / 1e9:.6f} s" for n, (t, c, _) in best))
                if events:
                    e = next((x for x in events if "custom-call" in x.name), events[0])
                    out.append(f"  device {i} {kind} stats of one: " + ", ".join(
                        f"{k}={str(v)[:80]!r}" for k, v in e.stats.items()))
        return "\n".join(out)

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, and the longest idle
        gaps, each named by the host event that overlaps it most."""
        by_name: dict[str, float] = {}
        for d in self.devices:
            for e in d.ops:
                t = e.clip(self.lo, self.hi)
                name = short_name(e.name)
                if t and not name.endswith(("-start", "-done")):  # async copies span steps
                    by_name[name] = by_name.get(name, 0.0) + t / 1e9
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        busy = self._busy(self.devices[0])
        edges = [self.lo] + [x for ab in busy for x in ab] + [self.hi]
        spans = sorted(
            ((a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a),
            key=lambda ab: ab[0] - ab[1],
        )[:top]
        host = [e for e in self.host if e.name != WINDOW]
        gaps = []
        for a, b in spans:
            best, best_t = "no host event", 0
            for e in host:
                t = e.clip(a, b)
                if t > best_t:
                    best, best_t = e.name, t
            gaps.append([best, (b - a) / 1e9])
        return {"device_ops": [list(kv) for kv in ops], "idle_gaps": gaps}
