#!/usr/bin/env python3
"""SHA-256 digests of what a run draws and counts for the Llama architecture:
``weights.make``'s leaves, ``reference.gaps``'s arrays (the float8 control
and the published-eps variant with them) and every ``work`` count, for two
seeds on ``test_bench.py``'s small architecture and on the smoke preset of
``smollm-chat``, with the counts and the weights table at published sizes.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/tests/digests.py

Prints one JSON object.  The process keeps to one CPU core: XLA's CPU
matrix products sum in an order that depends on its thread count, so the
reference's float32 logits are bit-stable only at a fixed count.
"""
from __future__ import annotations

import hashlib
import json
import os
import sys
from pathlib import Path

os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import reference  # noqa: E402
import run  # noqa: E402
import weights  # noqa: E402
from work import Work  # noqa: E402

ARCH = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "intermediate_size": 16, "num_hidden_layers": 3, "vocab_size": 100}
SMALL = {**ARCH, "padded_vocab": 128, "embed_std": 0.02, "tie_word_embeddings": False,
         "rms_norm_eps": 1e-06, "rope_theta": 10000.0}
SEEDS = (7, 2**31 + 12345)


def sha(parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(p if isinstance(p, bytes) else repr(p).encode())
    return h.hexdigest()


def arrays(tree):
    for path, x in sorted(jax.tree_util.tree_flatten_with_path(tree)[0],
                          key=lambda kv: jax.tree_util.keystr(kv[0])):
        x = np.asarray(x)
        yield jax.tree_util.keystr(path)
        yield str(x.dtype)
        yield x.shape
        yield x.tobytes()


def tokens(seed, vocab, n_prompt, n_served):
    rng = np.random.default_rng(seed)
    return rng.integers(1, vocab, n_prompt), rng.integers(0, vocab, n_served)


def work_parts(work, arch):
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    p, c = [5, 128, 1024, 1664], [7, 129, 2047, 1]
    yield work.dense_flops_per_token(arch)
    yield work.head_flops(arch)
    yield work.flash_attention(arch, p)
    yield work.paged_attention(arch, c)
    yield work.prefill_flops(arch, p)
    yield work.decode_flops(arch, c)
    yield work.least_time(*work.flash_attention(arch, p), peak)
    yield work.least_time(*work.paged_attention(arch, c), peak)


def digests() -> dict[str, str]:
    from repro.configs import get_smoke_config

    cell = run.Cell("smollm-chat", rehearse=True)
    model, work = cell.model, Work(cell.model)
    smoke = cell.arch(get_smoke_config(cell.config["program"]["arch"]))
    full = run.Cell("smollm-chat").arch()
    out = {}
    for name, (arch, pad_to, out_pad, n_prompt) in {
        "small": (SMALL, 32, 8, 12), "smoke": (smoke, 256, 24, 100),
    }.items():
        (variant,) = model.controls(arch, cell.config).values()
        for seed in SEEDS:
            w = weights.make(model.shapes(arch), seed)
            out[f"weights.{name}.{seed}"] = sha([json.dumps(arch, sort_keys=True), *arrays(w)])
            prompt, served = tokens(seed, arch["vocab_size"], n_prompt, out_pad)
            g = reference.gaps(model, w, arch, prompt, served, pad_to=pad_to, out_pad=out_pad,
                               control=True)
            g["published_eps"] = reference.gaps(model, w, variant, prompt, served, pad_to=pad_to,
                                                out_pad=out_pad)["served"]
            out[f"gaps.{name}.{seed}"] = sha(arrays(g))
        out[f"work.{name}"] = sha(work_parts(work, arch))
    out["work.full"] = sha(work_parts(work, full))
    out["arch.full"] = sha([json.dumps(full, sort_keys=True)])
    out["shapes.full"] = sha(sorted((k, repr(v)) for k, v in model.shapes(full).items()))
    return out


if __name__ == "__main__":
    print(json.dumps(digests(), indent=1))
