#!/usr/bin/env python3
"""Compile a configuration's served programs for a described TPU v5e, with
no chip attached, and print what the chip's compiler says of their memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/compile_v5e.py smollm-135m --prompt 1024

Builds the engine of ``configs/<name>.json`` on shapes only and compiles its
batched prefill at ``--prompt`` tokens, the admission insert, and the decode
step at the widest block table (``max_len`` / page size pages), each for one
chip of a ``v5e:2x2`` topology, then prints ``memory_analysis()`` of each and
whether the Pallas kernel is in it.  Nothing runs: this is the basis for the
slots and ``max_len`` of a configuration, not a measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("config")
    ap.add_argument("--prompt", type=int, required=True, help="prefill length compiled")
    a = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro.kernels.ops as ops
    from repro.configs import get_config
    from repro.dist.sharding import ParamSpec
    from repro.models.api import build_model
    from repro.serve.engine import ServeEngine, serve_context

    jax.config.update("jax_enable_compilation_cache", False)
    conf = json.loads((HERE / "configs" / f"{a.config}.json").read_text())
    eng = conf["engine"]
    slots, max_len, page = eng["slots"], eng["max_len"], eng["page_size"]
    ops._on_tpu = lambda: True  # the kernels' TPU branch, for the described chip
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    cfg = get_config(conf["program"]["arch"]).with_(**conf["program"]["overrides"])
    ctx = serve_context(cfg, use_kernels=eng["use_kernels"], page_size=page)
    model = build_model(ctx)

    def shape(s):
        return jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype), sharding=one)

    def spec(x):
        return isinstance(x, ParamSpec)

    params = jax.tree.map(shape, model.param_specs(), is_leaf=spec)
    engine = ServeEngine(ctx, None, slots=slots, max_len=max_len, page_size=page, eos_id=-1)
    pool = jax.tree.map(shape, engine._cache_specs, is_leaf=spec)

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)

    def nbytes(tree):
        return sum(x.size * jnp.dtype(x.dtype).itemsize for x in jax.tree.leaves(tree))

    print(f"{a.config}: {slots} slots, max_len {max_len}: weights {nbytes(params)} B, "
          f"page pool {nbytes(pool)} B", flush=True)

    def report(name, fn, *args, donate=()):
        c = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        m = c.memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes} B, outputs "
              f"{m.output_size_in_bytes} B, temporaries {m.temp_size_in_bytes} B, aliased "
              f"{m.alias_size_in_bytes} B, kernel {'tpu_custom_call' in c.as_text()}", flush=True)

    def prefill(p, t, n):
        return model.prefill_batch(p, t, n, max_len)

    report(f"prefill {slots} x {a.prompt}", prefill, params, i32(slots, a.prompt), i32(slots))
    caches = jax.eval_shape(prefill, params, i32(slots, a.prompt), i32(slots))[1]
    caches = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), caches)
    report("insert", engine._insert_body, pool, caches, i32(slots * (max_len // page)), donate=(0,))
    w = max_len // page
    report(f"decode at width {w}", engine._decode_paged_body, params, pool, i32(slots, w),
           i32(slots, 1), i32(slots), donate=(1,))
    return 0


if __name__ == "__main__":
    sys.exit(main())
