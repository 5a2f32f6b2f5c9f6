"""Self-checks of the on-chip benchmark, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

They are not among the repository's tier-1 tests (``testpaths`` is
``tests/``).  They check the arithmetic every run uses (percentiles, due
times, the work of each kernel), the trace reduction on a trace recorded
here, that every cell rehearses end to end and that no run off the chip
prints a result, and that the comparison deciding ``correct`` fails its
control and each planted fault.
"""
from __future__ import annotations

import json
import math
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import stats  # noqa: E402
import traffic  # noqa: E402
from work import Work  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _bench(script: str, *args: str, cwd=ROOT, timeout=900):
    return subprocess.run([sys.executable, str(Path(cwd) / "benchmarks" / "chip" / script), *args],
                          cwd=cwd, env=ENV, capture_output=True, text=True, timeout=timeout)


# -- arithmetic -----------------------------------------------------------------


def test_percentile_is_nearest_rank_and_missing_sorts_last():
    xs = list(range(1, 11))
    assert stats.percentile(xs, 90) == 9
    assert stats.percentile(xs, 99) == 10
    assert stats.percentile(xs, 50) == 5
    assert stats.percentile([3.0, math.inf, 1.0, 2.0], 90) == math.inf
    assert stats.beyond(100, 90) == 10
    assert stats.beyond(1000, 99) == 10


def test_spread_is_interquartile_over_median():
    vals = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    import statistics

    q1, med, q3 = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q3 - q1) / med)


def test_open_loop_due_times_span_the_window_and_every_seed_draws_the_same_sizes():
    mix = json.loads((HERE / "traffic" / "chat.json").read_text())
    rate, lead, secs = mix["arrivals"]["rate_per_s"], mix["lead_in_s"], 30.0
    a = traffic.open_loop(mix, secs, 7, 49152)
    b = traffic.open_loop(mix, secs, 2**31 + 12345, 49152)
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    for reqs in (a, b):
        due = [r.due for r in reqs]
        assert due[0] >= 0.0 and due == sorted(due) and due[-1] < lead + secs
        for r in reqs:
            assert len(r.prompt) % 128 == 0 and p["min"] <= len(r.prompt) <= p["max"]
            assert o["min"] <= r.max_new <= o["max"]
            assert r.prompt.min() >= 1 and r.prompt.max() < 49152
    win = [[r for r in reqs if r.due >= lead] for reqs in (a, b)]
    assert len(win[0]) == len(win[1]) == round(rate * secs)
    assert sorted(len(r.prompt) for r in win[0]) == sorted(len(r.prompt) for r in win[1])
    assert sorted(r.max_new for r in win[0]) == sorted(r.max_new for r in win[1])
    assert [len(r.prompt) for r in win[0]] != [len(r.prompt) for r in win[1]]
    again = traffic.open_loop(mix, secs, 7, 49152)
    assert all((x.prompt == y.prompt).all() and x.due == y.due for x, y in zip(a, again))


@pytest.mark.parametrize("secs", [10.0, 51.0])
def test_every_seed_offers_the_window_the_same_gaps_in_an_order_of_its_own(secs):
    mix = json.loads((HERE / "traffic" / "chat.json").read_text())
    lead = mix["lead_in_s"]

    def schedule(seed):
        reqs = traffic.open_loop(mix, secs, seed, 49152)
        win = [r for r in reqs if r.due >= lead]
        # each window arrival's gap to the next, the cycle closing
        nxt = [r.due for r in win[1:]] + [win[0].due + secs]
        return reqs, win, [round(t - r.due, 9) for r, t in zip(win, nxt)]

    (ra, wa, a), (rb, wb, b) = schedule(11), schedule(2**31 + 7)
    assert len(a) == round(mix["arrivals"]["rate_per_s"] * secs)
    assert sorted(a) == sorted(b) and a != b
    assert sum(a) == pytest.approx(secs)
    # the lead-in replays the cycle's end: each arrival before the window
    # has the size and the offset of one in the window, a cycle later
    for reqs, win in ((ra, wa), (rb, wb)):
        due = {(round(r.due, 6), len(r.prompt), r.max_new) for r in win}
        before = [r for r in reqs if r.due < lead]
        assert before
        for r in before:
            k = 1
            while r.due + k * secs < lead:
                k += 1
            assert (round(r.due + k * secs, 6), len(r.prompt), r.max_new) in due


def test_gaps_look_exponential_and_sizes_follow_the_mix():
    import statistics

    mix = json.loads((HERE / "traffic" / "chat.json").read_text())
    mix = {**mix, "arrivals": {**mix["arrivals"], "rate_per_s": 20.0}}
    reqs = [r for r in traffic.open_loop(mix, 100.0, 3, 1000) if r.due >= mix["lead_in_s"]]
    gaps = [y.due - x.due for x, y in zip(reqs, reqs[1:])]
    cv = statistics.pstdev(gaps) / statistics.mean(gaps)
    assert 0.9 < cv < 1.1  # Poisson: exponential gaps, CV 1
    med = statistics.median(len(r.prompt) for r in reqs)
    assert abs(med - mix["prompt_tokens"]["median"]) <= 128
    assert abs(statistics.median(r.max_new for r in reqs) - mix["output_tokens"]["median"]) <= 2


@pytest.mark.parametrize("mix_name", ["chat"])
def test_warmup_reaches_every_prefill_shape_and_block_table_width(mix_name):
    mix = json.loads((HERE / "traffic" / f"{mix_name}.json").read_text())
    max_len, page = 2048, 16
    warm = traffic.warmup(mix, 1000, max_len, page)

    def width(tokens):
        need, n = -(-tokens // page), 1
        while n < need:
            n *= 2
        return min(n, max_len // page)

    prompts = traffic.support(mix["prompt_tokens"])
    assert {len(r.prompt) for r in warm} == set(prompts)
    reached = {width(len(r.prompt) + k) for r in warm for k in range(1, r.max_new + 1)}
    longest = max(traffic.support(mix["output_tokens"]))
    wanted = {width(p + k) for p in prompts for k in range(1, longest + 1)}
    assert wanted <= reached


# -- work -----------------------------------------------------------------------

ARCH = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "intermediate_size": 16, "num_hidden_layers": 3, "vocab_size": 100}


def test_work_counts_by_hand():
    import run

    work = Work(run.load_arch("llama"))
    # head dim 4; per token per layer: q 8x8, k and v 8x4 each, o 8x8, mlp 3 x 8x16
    per_layer = 2 * (8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16)
    assert work.dense_flops_per_token(ARCH) == 3 * per_layer
    assert work.head_flops(ARCH) == 2 * 8 * 100
    # prompt of 5: causal pairs 15, QK and PV at 2 ops each per head dim, 2 heads
    f, b = work.flash_attention(ARCH, [5])
    assert f == 3 * 2 * 2 * 2 * 4 * 15
    assert b == 3 * 5 * (2 * 2 + 2 * 1) * 4 * 2
    # decode over 7 cached tokens: one query, 2 heads
    f, b = work.paged_attention(ARCH, [7])
    assert f == 3 * 2 * 2 * 2 * 4 * 7
    assert b == 3 * (2 * 7 * 1 * 4 + 2 * 2 * 4) * 2
    assert work.prefill_flops(ARCH, [5]) == 5 * 3 * per_layer + work.flash_attention(ARCH, [5])[0] + 1600
    assert work.decode_flops(ARCH, [7, 9]) == (
        2 * (3 * per_layer + 1600) + work.paged_attention(ARCH, [7, 9])[0])
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000.0, 50.0, peak) == (10.0, "compute")
    assert work.least_time(100.0, 50.0, peak) == (5.0, "memory")


def test_peak_table_is_keyed_by_device_kind_and_refuses_others():
    import run

    assert run.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(run.Fail):
        run.peaks_for("TPU v99")


# -- traces and readers ------------------------------------------------------------


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    import trace

    d = str(tmp_path_factory.mktemp("trace"))

    @jax.jit
    def _probe_step(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((128, 128))
    _probe_step(x).block_until_ready()
    with trace.capture(d):
        for _ in range(4):
            _probe_step(x).block_until_ready()
    return trace.Trace.load(d)


def test_trace_reduction_on_a_cpu_trace(cpu_trace):
    t = cpu_trace
    assert 0 < t.window_s
    assert 0 < t.busy_s() <= t.window_s
    mods = t.modules(("_probe_step",))
    assert len(mods) == 4
    assert t.modules(("_paged_kernel",)) == []
    inside = t.ops_in(("_probe_step",), ("",))
    assert inside and len(inside) == len(t.ops(("",)))
    assert t.ops_in(("_decode_paged_body",), ("",)) == []
    b = t.breakdown()
    assert b["device_ops"] and len(b["device_ops"]) <= 10
    assert len(b["idle_gaps"]) <= 10 and all(g[1] > 0 for g in b["idle_gaps"])
    assert "_probe_step" in t.describe()


def _window(traces, **kw):
    import run

    base = dict(counters=None, arch={**ARCH, "padded_vocab": 100},
                work=Work(run.load_arch("llama")), peak={
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, engine={"slots": 4},
        prefilled=[], decoded_ctx=[])
    base.update(kw)
    return run.Window(traces=traces, **base)


@pytest.mark.parametrize("metric", sorted(p.stem for p in (HERE / "metrics").glob("*.py")))
def test_reader_with_nothing_to_read_returns_none_not_zero(metric, cpu_trace):
    import run

    reader = run.load_reader(metric)
    assert reader.read(_window([])) is None
    if reader.EVENTS:  # names that a CPU trace never holds
        w = _window([cpu_trace], counters={"prefills": 3, "tokens": 9, "decode_steps": 2},
                    prefilled=[128], decoded_ctx=[129])
        assert reader.read(w) is None


def test_device_readers_on_a_cpu_trace(cpu_trace):
    import run

    w = _window([cpu_trace], prefilled=[128], decoded_ctx=[129])
    idle = run.load_reader("device_idle.chat").read(w)
    assert 0 <= idle < 100
    assert run.load_reader("step_mfu.chat").read(w) > 0
    assert w.mean_device_ms(("_probe_step",)) > 0


def test_counter_reader(cpu_trace):
    import run

    w = _window([cpu_trace], counters={"prefills": 6, "tokens": 106, "decode_steps": 25})
    assert run.load_reader("reqs_per_prefill.chat").read(w) is None  # no prefill program
    assert run.load_reader("reqs_per_prefill.chat").read(_window([])) is None


# -- the benchmark's file -------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_names_and_files():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    for c in BENCH["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert sorted(c["reduced"]) == sorted(f["reduced"])
        for key, cut in f["reduced"].items():
            assert f[key] == cut["run"] != cut["published"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert (HERE / "metrics" / f"{m['name']}.py").exists()
    for w in BENCH["workloads"]:
        assert (HERE / "traffic" / f"{w['traffic']}.json").exists()
        mine = [m for m in BENCH["per_layer"] if w["name"] in m.get("workloads", CELLS)]
        assert mine, w["name"]


def test_every_per_layer_metric_names_the_cells_it_reads_in():
    # a metric with no list would be owed by every cell added later
    for m in BENCH["per_layer"]:
        cells = m.get("workloads")
        assert isinstance(cells, list) and cells, m["name"]
        assert set(cells) <= set(CELLS), m["name"]


# -- whole runs off the chip -------------------------------------------------------------


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_rehearses_and_prints_no_result(cell):
    p = _bench("run.py", "--workload", cell, "--seed", str(2**31 + 3), "--seconds", "3",
               "--trace", "1", "--rehearse")
    assert p.returncode == 3, p.stderr[-3000:]
    assert p.stdout.strip() == ""
    assert "correct=True" in p.stderr


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    p = _bench("run.py", "--workload", CELLS[0], "--seed", "1", "--seconds", "2", "--trace", "0")
    assert p.returncode not in (0, 3)
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_a_directory_of_only_the_benchmark_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _bench("run.py", "--workload", CELLS[0], "--seed", "1", "--seconds", "2", "--trace", "0",
               cwd=tmp_path)
    assert p.returncode not in (0, 3)
    assert p.stdout.strip() == ""


# -- the comparison that decides correct ------------------------------------------------------


def test_the_control_comes_out_as_not_correct():
    p = _bench("run.py", "--workload", "smollm-chat", "--seed", "11", "--seconds", "3",
               "--trace", "0", "--rehearse", "--control")
    assert p.returncode == 3, p.stderr[-3000:]
    assert p.stdout.strip() == ""
    assert "correct=False" in p.stderr
    gap = re.search(r"check max_logit_gap: (\S+) \(limit", p.stderr)
    served = re.search(r"served tokens' widest gap (\S+);", p.stderr)
    assert float(gap.group(1)) > 3 * float(served.group(1))


# ``half_batch`` shows only where several slots decode at once, which the
# rehearsed chat (four slots, three seconds) rarely holds; it is read on the
# chip at the cell's size.
@pytest.mark.parametrize("fault", ["decode_state", "insert_state", "token"])
def test_each_fault_makes_the_run_incorrect(fault):
    p = _bench("faults.py", "--workload", "smollm-chat", "--fault", fault, "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    row = json.loads(p.stdout.strip().splitlines()[-1])
    assert row["correct"] is False, row
