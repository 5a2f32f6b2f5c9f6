"""repro.dist.sharding contract tests: profile resolution, counts, init.

logical_to_spec accepts a plain ``{axis: size}`` mapping wherever a Mesh is
expected, so production-mesh-shaped resolution is testable on a 1-device
box (the real 16×16 / 2×16×16 meshes only exist under the dry-run's forced
device count).
"""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke_config
from repro.dist.sharding import (
    DEFAULT_RULES,
    FLAT_DP_RULES,
    MULTIPOD_RULES,
    RULE_PROFILES,
    count_params,
    logical_to_spec,
    materialize_params,
)
from repro.launch.mesh import make_host_mesh, make_mesh, rules_for
from repro.models.api import build_model
from repro.models.layers import ModelContext

POD = {"data": 16, "model": 16}
MULTIPOD = {"pod": 2, "data": 16, "model": 16}


class TestProfileResolution:
    def test_default_tp_and_dp(self):
        assert logical_to_spec((4096, 1536), ("embed", "mlp"), DEFAULT_RULES, POD) \
            == P(None, "model")
        assert logical_to_spec((256, 4096), ("batch", None), DEFAULT_RULES, POD) \
            == P("data", None)
        assert logical_to_spec((49408, 512), ("vocab", "embed"), DEFAULT_RULES, POD) \
            == P("model", None)

    def test_indivisible_dims_replicate(self):
        # smollm: 9 heads / 3 kv heads on a 16-way model axis → replicated
        assert logical_to_spec((576, 9, 64), ("embed", "heads", None),
                               DEFAULT_RULES, POD) == P(None, None, None)
        assert logical_to_spec((576, 3, 64), ("embed", "kv_heads", None),
                               DEFAULT_RULES, POD) == P(None, None, None)

    def test_multipod_batch_spans_pod_and_data(self):
        assert logical_to_spec((256, 4096), ("batch", None),
                               MULTIPOD_RULES, MULTIPOD) == P(("pod", "data"), None)
        # same rules degrade on a pod-less mesh: pod axis dropped
        assert logical_to_spec((256, 4096), ("batch", None),
                               MULTIPOD_RULES, POD) == P("data", None)

    def test_flat_dp_replicates_params(self):
        assert logical_to_spec((256, 128), ("batch", None), FLAT_DP_RULES, POD) \
            == P(("data", "model"), None)
        assert logical_to_spec((512, 2048), ("embed", "mlp"), FLAT_DP_RULES, POD) \
            == P(None, None)

    def test_serve_kv_seq_wins_model_axis(self):
        serve, _ = RULE_PROFILES["serve"]
        spec = logical_to_spec((32, 4096, 16, 64),
                               ("batch", "kv_seq", "kv_heads", None), serve, POD)
        # kv_seq takes the model axis; kv_heads must not reuse it
        assert spec == P("data", "model", None, None)

    def test_no_mesh_axis_used_twice(self):
        # rwkv channel-mix wr is (E, E) with embed on both sides under a
        # profile that shards embed: the second occurrence must replicate
        rules = DEFAULT_RULES.with_("fsdp-ish", embed=("model",),
                                    embed2=("model",))
        spec = logical_to_spec((512, 512), ("embed", "embed2"), rules, POD)
        assert spec == P("model", None)

    def test_every_profile_resolves_on_host_mesh(self):
        mesh = make_host_mesh()
        for name, (pod_rules, multipod_rules) in RULE_PROFILES.items():
            assert rules_for(mesh, name) is pod_rules
            for rules in (pod_rules, multipod_rules):
                spec = logical_to_spec((256, 64), ("batch", "embed"), rules, mesh)
                assert isinstance(spec, P)


class TestShardConstraint:
    def test_one_device_noop_warns_once(self):
        """The 1-device drop is explicit: one warning per process, then
        silent — and the value passes through untouched."""
        import warnings

        from repro.dist import sharding as sh

        mesh = make_host_mesh()
        x = np.ones((8, 4), np.float32)
        old = sh._noop_constraint_warned
        try:
            sh._noop_constraint_warned = False
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                y = sh.shard_constraint(x, ("batch", None), DEFAULT_RULES, mesh)
                sh.shard_constraint(x, ("batch", None), DEFAULT_RULES, mesh)
            assert y is x  # no-op returns the operand itself
            msgs = [str(m.message) for m in w if "shard_constraint" in str(m.message)]
            assert len(msgs) == 1  # warned exactly once
            assert "no-op" in msgs[0]
        finally:
            sh._noop_constraint_warned = old

    def test_multi_device_places_real_constraint(self):
        """Dry-run under a forced 4-device mesh: the lowered program carries
        a sharding constraint and the constrained output lands sharded over
        the data axis (subprocess — the main process must keep 1 device)."""
        import os
        import subprocess
        import sys
        import textwrap

        body = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import jax, jax.numpy as jnp, numpy as np
            from repro.dist.sharding import DEFAULT_RULES, shard_constraint
            from repro.launch.mesh import make_mesh

            mesh = make_mesh((4, 1), ("data", "model"))

            def f(x):
                return shard_constraint(x, ("batch", None), DEFAULT_RULES, mesh)

            x = jnp.zeros((8, 4), jnp.float32)
            txt = jax.jit(f).lower(x).as_text()
            assert "sdy.sharding_constraint" in txt, txt  # reached the HLO
            out = jax.jit(f)(x)
            shards = {s.device.id: s.index for s in out.addressable_shards}
            assert len(shards) == 4  # one shard per device over batch
            rows = sorted(idx[0].start or 0 for idx in shards.values())
            assert rows == [0, 2, 4, 6], rows
            print("OK")
        """)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src"),
             env.get("PYTHONPATH", "")]
        )
        r = subprocess.run([sys.executable, "-c", body], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "OK" in r.stdout


class TestCountParams:
    @pytest.mark.parametrize("name,lo,hi", [
        ("smollm-135m", 5e4, 5e6),
        ("granite-moe-1b-a400m", 5e4, 2e7),
    ])
    def test_count_matches_materialized_size(self, name, lo, hi):
        cfg = get_smoke_config(name)
        ctx = ModelContext(cfg, make_host_mesh(), DEFAULT_RULES)
        specs = build_model(ctx).param_specs()
        n = count_params(specs)
        assert lo < n < hi
        params = materialize_params(specs, jax.random.PRNGKey(0))
        assert n == sum(int(np.asarray(x).size) for x in jax.tree.leaves(params))


class TestMaterializeDeterminism:
    def test_same_seed_identical_leaves(self):
        cfg = get_smoke_config("smollm-135m")
        ctx = ModelContext(cfg, make_host_mesh(), DEFAULT_RULES)
        specs = build_model(ctx).param_specs()
        a = materialize_params(specs, jax.random.PRNGKey(7))
        b = materialize_params(specs, jax.random.PRNGKey(7))
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
            a, b,
        )
        c = materialize_params(specs, jax.random.PRNGKey(8))
        diffs = [
            not np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(c))
            if np.asarray(x).ndim >= 2 and np.asarray(x).std() > 0
        ]
        assert any(diffs)  # a different seed actually changes weights

    def test_mesh_shape_independent(self):
        """Init depends only on (seed, path): identical under any mesh/rules."""
        cfg = get_smoke_config("smollm-135m")
        specs = build_model(
            ModelContext(cfg, make_host_mesh(), DEFAULT_RULES)
        ).param_specs()
        with make_host_mesh():
            a = materialize_params(specs, jax.random.PRNGKey(0))
        with make_mesh((1,), ("model",)):
            b = materialize_params(specs, jax.random.PRNGKey(0))
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
            a, b,
        )

    def test_profile_mesh_matrix_bitwise_identical(self):
        """Across every RULE_PROFILES profile × mesh shape, materialized
        (and device_put-sharded) params are bitwise the same logical
        arrays — the exact invariant the PR 4 remesh driver and resharded
        checkpoint restore rely on (a remesh may swap both the mesh axes
        and the rules profile; the weights must not move a ULP)."""
        from repro.dist.sharding import ParamSpec, sharding_tree

        specs = {
            "emb": ParamSpec((64, 32), ("vocab", "embed"), np.float32, 0.02),
            "w": ParamSpec((32, 128), ("embed", "mlp"), np.float32),
            "heads": ParamSpec((32, 4, 8), ("embed", "heads", None), np.float32),
            "scale": ParamSpec((32,), ("embed",), np.float32, 1.0),
        }
        ref = jax.tree.map(
            np.asarray, materialize_params(specs, jax.random.PRNGKey(3))
        )
        meshes = [
            make_mesh((1, 1), ("data", "model")),
            make_mesh((1,), ("model",)),
            make_mesh((1, 1, 1), ("pod", "data", "model")),
        ]
        for profile in RULE_PROFILES:
            for mesh in meshes:
                rules = rules_for(mesh, profile)
                with mesh:
                    params = materialize_params(specs, jax.random.PRNGKey(3))
                    placed = jax.device_put(
                        params, sharding_tree(specs, rules, mesh)
                    )
                jax.tree.map(
                    lambda r, x: np.testing.assert_array_equal(r, np.asarray(x)),
                    ref, placed,
                )

    def test_init_scale_semantics(self):
        from repro.dist.sharding import ParamSpec

        specs = {
            "scale": ParamSpec((16,), (None,), np.float32, init_scale=1.0),
            "bias": ParamSpec((16,), (None,), np.float32, init_scale=0.0),
            "cache": ParamSpec((2, 8, 4), ("batch", None, None), np.float32, 0.0),
            "emb": ParamSpec((64, 32), ("vocab", "embed"), np.float32, 0.02),
            "w": ParamSpec((64, 32), ("embed", "mlp"), np.float32),
        }
        p = materialize_params(specs, jax.random.PRNGKey(0))
        assert np.all(np.asarray(p["scale"]) == 1.0)
        assert np.all(np.asarray(p["bias"]) == 0.0)
        assert np.all(np.asarray(p["cache"]) == 0.0)
        assert 0.01 < np.asarray(p["emb"]).std() < 0.03
        assert 0.06 < np.asarray(p["w"]).std() < 0.25  # ≈ 1/√64
