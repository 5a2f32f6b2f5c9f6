"""Suite bootstrap: src/ on sys.path, multiproc guard, and the ProxySan
plugin.

The sys.path insert duplicates pyproject's ``pythonpath`` on purpose: this
conftest imports ``repro`` itself (for the sanitizer) and must not depend on
ini-option processing order.

``@pytest.mark.multiproc`` tests spawn subprocesses (lease workers, chaos
victims) and could wedge the tier-1 gate if a child never writes the key
the parent is blocked on.  A SIGALRM watchdog turns any such hang into a
prompt failure: default 120 s per test, raised per-test via
``pytest.mark.multiproc(timeout=...)``; the ``REPRO_MULTIPROC_TIMEOUT``
env var, when set, is a hard *cap* over both (scripts/check.sh sets it so
the gate's worst-case hang is bounded regardless of per-test budgets).

ProxySan plugin (``REPRO_PROXYSAN=1``): the whole suite runs under the
runtime sanitizer — every test fails on any *new* lifecycle violation
(use-after-evict, double-free, refcount underflow, stale cache read) it
caused, and the session exits non-zero if any Owned cell is still
resident after the last test.  ``scripts/check.sh`` sets the env var for
the tier-1 step; tests that exercise the failure paths on purpose scope
them with ``sanitize.expecting()``.  (Object-payload leak reports stay
per-scope — see test_proxysan.py — because many tests legitimately leave
payloads in stores they then drop whole.)
"""
import os
import signal
import sys

import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import hypothesis  # noqa: E402,F401


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multiproc(timeout=120): spawns subprocesses; a SIGALRM watchdog "
        "fails the test after `timeout` seconds instead of wedging the gate",
    )


# -- ProxySan plugin ---------------------------------------------------------

from repro.core import sanitize as _sanitize  # noqa: E402


@pytest.fixture
def san():
    """The process sanitizer, state-snapshotted and restored around the
    test: nothing a test mints (or the violations it provokes on purpose)
    can bleed into the session gate or into other tests."""
    s = _sanitize._get()
    snap = (
        s.enabled,
        (set(s._opted), set(s._opted_out)),
        len(s.violations),
        set(s._live),
        set(s._freed),
        set(s._put_seq),
        set(s._fill_seq),
        set(s._borrows),
        dict(s.counters),
    )
    yield s
    with s._lock:
        s.enabled = snap[0]
        s._opted.clear()
        s._opted.update(snap[1][0])
        s._opted_out.clear()
        s._opted_out.update(snap[1][1])
        del s.violations[snap[2]:]
        for attr, keep in (
            ("_live", snap[3]),
            ("_freed", snap[4]),
            ("_put_seq", snap[5]),
            ("_fill_seq", snap[6]),
            ("_borrows", snap[7]),
        ):
            table = getattr(s, attr)
            for k in [k for k in table if k not in keep]:
                table.pop(k, None)
        s.counters.clear()
        s.counters.update(snap[8])


@pytest.fixture(autouse=True)
def _proxysan_guard():
    """Fail any test that caused a new sanitizer violation."""
    san = _sanitize.current()
    if san is None:
        yield
        return
    before = len(san.violations)
    yield
    new = san.violations[before:]
    assert not new, (
        f"ProxySan recorded {len(new)} violation(s) during this test:\n"
        + "\n".join(v.render() for v in new)
        + "\n(intentional misuse? scope it with sanitize.expecting())"
    )


def pytest_sessionfinish(session, exitstatus):
    """Sanitizer-clean gate: no violations, no leaked Owned cells."""
    san = _sanitize.current()
    if san is None:
        return
    import gc

    gc.collect()  # drop cycles so owner __del__ frees run before the report
    problems = [v.render() for v in san.violations]
    problems += [
        f"[proxysan:leak] owned cell {l['key']!r} in store {l['store']!r} "
        f"never freed\n  minted at:\n{l['minted_at']}"
        for l in san.leak_report(kinds=("owned",))
    ]
    if problems:
        tr = session.config.pluginmanager.get_plugin("terminalreporter")
        write = tr.write_line if tr is not None else print
        write("")
        write(f"ProxySan session gate: {len(problems)} problem(s)")
        for p in problems:
            write(p)
        # wrap_session returns session.exitstatus *after* this hook runs
        session.exitstatus = max(int(exitstatus) or 0, 1)
        session.testsfailed += 1


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("multiproc")
    if marker is None or not hasattr(signal, "SIGALRM"):
        return (yield)
    timeout = int(marker.kwargs.get("timeout", 120))
    cap = os.environ.get("REPRO_MULTIPROC_TIMEOUT")
    if cap is not None:
        timeout = min(timeout, int(cap))  # env is a hard cap, not a default

    def on_alarm(signum, frame):
        raise TimeoutError(
            f"multiproc test exceeded its {timeout}s watchdog "
            f"(a subprocess is likely wedged): {item.nodeid}"
        )

    old_handler = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(timeout)
    try:
        return (yield)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old_handler)
