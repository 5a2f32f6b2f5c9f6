"""Distributed-optimization example: int8 error-feedback gradient all-reduce.

Data-parallel training over a 4-device host mesh via shard_map, comparing
exact f32 gradient pmean vs the int8 error-feedback compressed_psum
(`repro.optim.grad_compress`). On the production multi-pod mesh this is the
pod-axis (DCN, 25 GB/s) collective — compressing it 4× moves the §Roofline
DCN term directly.

    PYTHONPATH=src python examples/grad_compression_dp.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke_config
from repro.dist.sharding import materialize_params
from repro.launch.mesh import make_mesh, rules_for
from repro.models.api import build_model, synth_batch
from repro.models.layers import ModelContext
from repro.optim.grad_compress import tree_compressed_pmean


def main() -> int:
    mesh = make_mesh((4, 1), ("data", "model"))
    cfg = get_smoke_config("smollm-135m")
    rules = rules_for(mesh)
    with mesh:
        ctx = ModelContext(cfg, mesh, rules)
        model = build_model(ctx)
        params0 = materialize_params(model.param_specs(), jax.random.PRNGKey(0))
        lr = 0.5  # plain SGD on the smoke model needs a big step to move

        def make_step(compress: bool):
            @functools.partial(
                jax.shard_map, mesh=mesh,
                in_specs=(P(), P("data"), P()),
                out_specs=(P(), P(), P()),
                check_vma=False,
            )
            def step(params, batch, errs):
                loss, grads = jax.value_and_grad(
                    lambda p: model.loss(p, batch)[0]
                )(params)
                if compress:
                    grads, errs = tree_compressed_pmean(grads, errs, "data")
                else:
                    grads = jax.tree.map(
                        lambda g: jax.lax.pmean(g, "data"), grads
                    )
                new_params = jax.tree.map(
                    lambda p, g: p - lr * g.astype(p.dtype), params, grads
                )
                loss = jax.lax.pmean(loss, "data")
                return new_params, loss, errs

            return jax.jit(step)

        results = {}
        for compress in (False, True):
            params = params0
            errs = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params0
            )
            step = make_step(compress)
            losses = []
            t0 = time.perf_counter()
            for i in range(30):
                batch = synth_batch(cfg, 8, 64, rng=i)
                params, loss, errs = step(params, batch, errs)
                losses.append(float(loss))
            dt = time.perf_counter() - t0
            results[compress] = (losses, dt)

        l_exact, _ = results[False]
        l_comp, _ = results[True]
        n_params = sum(x.size for x in jax.tree.leaves(params0))
        wire_exact = n_params * 4          # f32 grads
        wire_comp = n_params * 1 + 4       # int8 + one scale/tensor (≈)
        print("grad_compression_dp (4-way DP, smollm smoke):")
        print(f"  exact  loss: first {l_exact[0]:.3f} last {l_exact[-1]:.3f}")
        print(f"  int8EF loss: first {l_comp[0]:.3f} last {l_comp[-1]:.3f}")
        gap = abs(l_comp[-1] - l_exact[-1])
        print(f"  final-loss gap: {gap:.4f} (error feedback keeps parity)")
        print(f"  gradient wire bytes: {wire_exact/1e6:.1f} MB -> "
              f"{wire_comp/1e6:.1f} MB per step ({wire_exact/wire_comp:.1f}x)")
        assert gap < 0.15, "compressed training diverged from exact"
        return 0


if __name__ == "__main__":
    raise SystemExit(main())
