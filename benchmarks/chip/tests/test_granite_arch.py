"""Self-checks of ``archs/granitemoehybrid.py`` and the ``granite4h-chat2k``
cell, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

The module defines the interface the harness calls; ``check`` holds the
program to the configuration file (the layer pattern included); its weight
table is the program's parameter layout; its counts agree with counts made
by hand; and at the smoke preset the comparison that decides ``correct``
fails both controls (float8 picks, Mamba-2 state zeroed where decoding
begins) while the served tokens pass.
"""
from __future__ import annotations

import json
import math
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}
CELL = "granite4h-chat2k"
CONFIG = json.loads((HERE / "configs" / "granite-4.0-h-micro.json").read_text())


@pytest.fixture(scope="module")
def arch_mod():
    import run

    return run.load_arch("granitemoehybrid")


def _arch(config=CONFIG):
    a = {k: v for k, v in config.items() if not isinstance(v, (dict, list))}
    a.update(config["weights"], padded_vocab=config["program"]["padded_vocab"])
    return a


def test_the_module_defines_the_interface(arch_mod):
    import run

    for name in run.ARCH_NAMES + ("gaps", "ssd_scan", "decode_hbm_bytes", "reference_logits"):
        assert callable(getattr(arch_mod, name, None)), name


def test_check_holds_the_program_to_the_file(arch_mod):
    from repro.configs import get_config

    cfg = get_config(CONFIG["program"]["arch"])
    assert arch_mod.check(cfg, CONFIG) is None
    shifted = dict(CONFIG, attn_layer_offset=4)
    assert "do not reproduce layer_types" in arch_mod.check(cfg, shifted)
    sparse = dict(CONFIG, attn_layer_period=20)
    assert "do not reproduce layer_types" in arch_mod.check(cfg, sparse)
    assert arch_mod.check(cfg.with_(attention_multiplier=0.125), CONFIG)
    assert arch_mod.check(cfg.with_(ssm_conv_bias=False), CONFIG)
    assert arch_mod.check(cfg, dict(CONFIG, num_local_experts=8))


@pytest.mark.parametrize("size", ["smoke", "full"])
def test_the_weight_table_is_the_program_layout(arch_mod, size):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, get_smoke_config
    from repro.dist.sharding import ParamSpec
    from repro.models.api import build_model
    from repro.serve.engine import serve_context

    name = CONFIG["program"]["arch"]
    cfg = get_smoke_config(name) if size == "smoke" else get_config(name)
    arch = arch_mod.rehearse(_arch(), cfg) if size == "smoke" else _arch()
    specs = build_model(serve_context(cfg)).param_specs()
    flat = jax.tree_util.tree_flatten_with_path(specs, is_leaf=lambda x: isinstance(x, ParamSpec))[0]
    want = {tuple(k.key for k in path): (tuple(s.shape), jnp.dtype(s.dtype)) for path, s in flat}
    got = {path: (shape, jnp.dtype(dtype)) for path, (shape, dtype, _, _) in
           arch_mod.shapes(arch).items()}
    assert got == want
    n = sum(math.prod(s) for s, _ in want.values())
    assert arch_mod.weight_bytes(arch) == sum(math.prod(s) * d.itemsize for s, d in want.values())
    if size == "full":
        assert 3.1e9 < n < 3.3e9  # about 3.19 B parameters


# a tiny arch: E 8, attention 2 heads over 1 of 4, MLP 16, one period of four
# layers (attention at 1), Mamba-2 inner 16 = 4 heads of 4, d_state 2, conv 4
TINY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "shared_intermediate_size": 16, "num_hidden_layers": 4, "vocab_size": 100,
        "padded_vocab": 256, "attn_layer_period": 4, "attn_layer_offset": 1,
        "mamba_expand": 2, "mamba_n_heads": 4, "mamba_d_head": 4, "mamba_d_state": 2,
        "mamba_d_conv": 4, "mamba_conv_bias": True, "tie_word_embeddings": True,
        "embed_std": 0.005}


def test_counts_by_hand(arch_mod):
    from work import Work

    work = Work(arch_mod)
    mlp = 3 * 8 * 16  # wi, wg, wo on every layer
    attn = 8 * 8 + 2 * 8 * 4 + 8 * 8  # q; k and v; o
    # in_proj to z, x (16 each), B, C (2 each), dt (4); out_proj; conv over
    # 16 + 2 + 2 channels of 4 taps; recurrence 2 multiply-adds a state element
    mamba = 8 * (16 + 16 + 2 + 2 + 4) + 16 * 8 + 4 * 20 + 2 * 4 * 4 * 2
    assert work.dense_flops_per_token(TINY) == 2 * (4 * mlp + attn + 3 * mamba)
    assert work.head_flops(TINY) == 2 * 8 * 100
    # attention in the one attention layer only
    assert work.flash_attention(TINY, [5]) == (2 * 2 * 2 * 4 * 15, 5 * (2 * 2 + 2 * 1) * 4 * 2)
    assert work.paged_attention(TINY, [7]) == (2 * 2 * 2 * 4 * 7, (2 * 7 * 1 * 4 + 2 * 2 * 4) * 2)
    # one chunk of 3 tokens in each of 3 Mamba-2 layers, 4 heads: C.h and the
    # state update 2cNP each, the causal tile and its combine c(c+1)/2 pairs
    # at 2N and 2P; bytes: x, y, dt, la per head, B and C, the final state
    flops, bytes_ = arch_mod.ssd_scan(TINY, [3])
    assert flops == 3 * 4 * (4 * 3 * 2 * 4 + 3 * 4 * (2 + 4))
    assert bytes_ == 3 * (3 * 4 * (2 * 4 * 2 + 2 * 4) + 3 * 2 * 2 * 2 + 4 * 4 * 2 * 4)
    two_chunks, _ = arch_mod.ssd_scan(TINY, [130])
    assert two_chunks == 3 * 4 * ((4 * 128 * 8 + 128 * 129 * 6) + (4 * 2 * 8 + 2 * 3 * 6))
    # conv inputs (3 x 20, bf16) and SSD state (4 x 4 x 2, f32) of 3 layers
    row = 3 * (3 * 20 * 2 + 4 * 4 * 2 * 4)
    assert arch_mod.state_row_bytes(TINY) == row
    # two steps decoding three tokens over 5 + 6 + 7 keys: K/V as the paged kernel reads it
    _, kv = work.paged_attention(TINY, [5, 6, 7])
    assert kv == (2 * 18 * 1 * 4 + 3 * 2 * 2 * 4) * 2
    assert arch_mod.decode_hbm_bytes(TINY, 2, 3, 18) == 2 * arch_mod.weight_bytes(TINY) + kv + 3 * 2 * row


MS = 1_000_000


def test_decode_hbm_reader_counts_only_the_window(arch_mod):
    """Programs and emit spans outside the 10-s window count on neither side."""
    import run
    import trace
    from work import Work

    t = trace.Trace.__new__(trace.Trace)
    t.lo, t.hi = 10 * MS, 30 * MS
    progs = [(5, 9), (10, 14), (20, 24), (30, 34)]  # the first and last lie outside
    t.devices = [trace.DeviceTrace([], [trace.Event("_decode_state_body", a * MS, b * MS,
                                                    "jit__decode_state_body", {})
                                        for a, b in progs])]
    t.host = [trace.Event("serve.decode.emit", (b + 1) * MS, (b + 2) * MS, "serve.decode.emit",
                          {"step": i, "tokens": 2, "ctx": 100 + i})
              for i, (_, b) in enumerate(progs)]  # emits at 10, 15, 25, 35 ms
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    w = run.Window(traces=[t], counters={"decode_steps": 40}, arch=TINY, work=Work(arch_mod),
                   peak=peak, engine={}, prefilled=[], decoded_ctx=[1000] * 80)
    got = run.load_reader("decode_hbm_roofline.chat2k").read(w)
    least = arch_mod.decode_hbm_bytes(TINY, 2, 6, 100 + 101 + 102)  # programs 1, 2; emits 0-2
    assert got == pytest.approx(100 * least / (8e-3 * 819e9))
    for e in t.host:  # a program without the stat: nothing to read
        e.stats.pop("ctx")
    assert run.load_reader("decode_hbm_roofline.chat2k").read(w) is None


def test_state_rows_reader():
    import run

    reader = run.load_reader("state_rows_per_step.chat2k")
    w = run.Window(traces=[], counters={"decode_steps": 4, "state_row_steps": 128}, arch={},
                   work=None, peak={}, engine={}, prefilled=[], decoded_ctx=[])
    assert reader.read(w) == 32
    w.counters = {"decode_steps": 4}  # a program without state slots
    assert reader.read(w) is None


def _rehearse(seed: int, *args: str):
    return subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", CELL, "--seed", str(seed),
         "--seconds", "3", "--rehearse", *args],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=900)


def test_the_cell_rehearses_and_its_counter_reader_reads():
    p = _rehearse(2**31 + 41, "--trace", "1")
    assert p.returncode == 3, p.stderr[-3000:]
    assert p.stdout.strip() == ""
    assert "correct=True" in p.stderr
    # the device readers find no kernel or program names off the chip; the
    # counter reader does
    assert "state_rows_per_step.chat2k found nothing" not in p.stderr


@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_both_controls_read_above_the_limit_on_the_cpu(seed):
    p = _rehearse(seed, "--trace", "0", "--control")
    assert p.returncode == 3, p.stderr[-3000:]
    assert "correct=False" in p.stderr
    limit = CONFIG["check"]["rehearse_max_logit_gap"]
    fp8 = float(re.search(r"check max_logit_gap: (\S+) \(limit", p.stderr).group(1))
    served = float(re.search(r"served tokens' widest gap (\S+);", p.stderr).group(1))
    reset = float(re.search(r"state_reset (\S+?)[,\s]", p.stderr).group(1))
    assert served < limit < fp8
    assert limit < reset
