"""int8 error-feedback gradient compression (optim/grad_compress.py).

Unit behaviour (quantize/dequant, error carry) runs in-process; the
shard_map integration tests need a 4-device mesh, so they run in a
subprocess with ``--xla_force_host_platform_device_count=4`` (the main
pytest process must keep seeing 1 device — see launch/dryrun.py).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.grad_compress import (
    compress_with_feedback,
    dequantize_int8,
    quantize_int8,
)


def test_quantize_roundtrip_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 64), jnp.float32)
    q, s = quantize_int8(x)
    assert q.dtype == jnp.int8
    err = np.abs(np.asarray(dequantize_int8(q, s) - x))
    assert err.max() <= float(s) / 2 + 1e-7  # half-ulp of the quant grid


def test_error_feedback_carries_residual():
    x = jnp.full((8,), 0.3, jnp.float32)
    err = jnp.zeros((8,), jnp.float32)
    q, s, new_err = compress_with_feedback(x, err)
    recon = dequantize_int8(q, s)
    np.testing.assert_allclose(np.asarray(recon + new_err), np.asarray(x),
                               rtol=1e-6, atol=1e-6)


_SUBPROCESS_BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.optim.grad_compress import compressed_psum, tree_compressed_pmean

    mesh = make_mesh((4,), ("data",))

    # 1) compressed psum tracks the exact mean within the quant grid
    g = jax.random.normal(jax.random.PRNGKey(1), (4, 32), jnp.float32)
    e = jnp.zeros_like(g)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                       out_specs=(P("data"), P("data")), check_vma=False)
    def run(gl, el):
        m, ne = compressed_psum(gl[0], el[0], "data")
        return m[None], ne[None]

    mean, _ = run(g, e)
    exact = g.mean(0)
    for i in range(4):
        np.testing.assert_allclose(np.asarray(mean[i]), np.asarray(exact),
                                   atol=5e-2, rtol=0)

    # 2) error feedback: accumulated compressed mean converges to exact
    steps, shards, dim = 20, 4, 16
    gs = jax.random.normal(jax.random.PRNGKey(2), (steps, shards, dim), jnp.float32)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(None, "data"), P("data")),
                       out_specs=(P(None, "data"), P("data")), check_vma=False)
    def run_all(g_seq, e0):
        def body(e, g):
            m, ne = compressed_psum(g, e, "data")
            return ne, m
        eT, ms = jax.lax.scan(body, e0[0], g_seq[:, 0])
        return ms[:, None], eT[None]

    ms, _ = run_all(gs, jnp.zeros((shards, dim), jnp.float32))
    acc_comp = np.asarray(ms[:, 0].sum(0))
    acc_exact = np.asarray(gs.mean(1).sum(0))
    np.testing.assert_allclose(acc_comp, acc_exact, atol=6e-2, rtol=0)

    # 3) tree wrapper preserves structure
    tree = {"a": jnp.ones((4, 8)), "b": {"c": jnp.ones((4, 3))}}
    errs = jax.tree.map(jnp.zeros_like, tree)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P("data"), P("data")),
                       out_specs=(P("data"), P("data")), check_vma=False)
    def run_tree(t, e):
        tl = jax.tree.map(lambda x: x[0], t)
        el = jax.tree.map(lambda x: x[0], e)
        m, ne = tree_compressed_pmean(tl, el, "data")
        return (jax.tree.map(lambda x: x[None], m),
                jax.tree.map(lambda x: x[None], ne))

    m, ne = run_tree(tree, errs)
    assert jax.tree.structure(m) == jax.tree.structure(tree)
    np.testing.assert_allclose(np.asarray(m["a"][0]), np.ones((8,)), atol=1e-2)
    print("SHARD_MAP_GRAD_COMPRESS_OK")
""")


def test_compressed_psum_shard_map_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_BODY],
        capture_output=True, text=True, timeout=240,
        cwd=os.path.join(os.path.dirname(__file__), ".."), env=env,
    )
    assert "SHARD_MAP_GRAD_COMPRESS_OK" in out.stdout, (
        out.stdout[-2000:] + "\n" + out.stderr[-2000:]
    )
