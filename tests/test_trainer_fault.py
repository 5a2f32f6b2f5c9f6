"""Fault-tolerance tests: checkpoint/restart, elastic re-mesh, stragglers,
heartbeats, shard redispatch — the large-scale-runnability substrate."""
from __future__ import annotations

import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.ckpt.checkpoint import CheckpointManager
from repro.configs import get_smoke_config
from repro.core.proxy import extract
from repro.core.store import Store
from repro.data.pipeline import (
    DispatchingDataLoader,
    StreamingDataLoader,
    SyntheticCorpus,
)
from repro.dist.fault import HeartbeatMonitor, StragglerPolicy, elastic_plan
from repro.dist.sharding import materialize_params
from repro.launch.mesh import make_host_mesh, make_mesh, rules_for
from repro.models.api import build_model
from repro.models.layers import ModelContext
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


@pytest.fixture(scope="module")
def ctx():
    cfg = get_smoke_config("smollm-135m")
    mesh = make_host_mesh()
    return ModelContext(cfg, mesh, rules_for(mesh))


def make_trainer(ctx, tmp, **kw):
    tc = TrainerConfig(
        opt=AdamWConfig(lr=1e-3, warmup_steps=2),
        ckpt_every=kw.pop("ckpt_every", 3),
        ckpt_dir=str(tmp),
        log_every=1000,
        **kw,
    )
    return Trainer(ctx, tc)


def data(ctx, n):
    corpus = SyntheticCorpus(ctx.cfg, 2, 32)
    return [corpus.next_batch(i) for i in range(n)]


class TestCheckpoint:
    def test_save_restore_roundtrip(self, ctx, tmp_path):
        model = build_model(ctx)
        params = materialize_params(model.param_specs(), jax.random.PRNGKey(0))
        mgr = CheckpointManager(str(tmp_path), keep=2)
        mgr.save(params, step=7)
        restored, step = mgr.restore(params)
        assert step == 7
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
            params, restored,
        )
        mgr.close()

    def test_async_save_overlaps_and_retention(self, ctx, tmp_path):
        model = build_model(ctx)
        params = materialize_params(model.param_specs(), jax.random.PRNGKey(0))
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for s in (1, 2, 3, 4):
            fut = mgr.save_async(params, step=s)
            assert fut is not None
        mgr.wait()
        mgr.wait()  # idempotent
        steps = sorted(
            int(f.split("-")[1].split(".")[0])
            for f in os.listdir(tmp_path) if f.startswith("manifest-")
        )
        assert steps == [3, 4]  # keep-last-2 enforced by ownership frees
        mgr.close()

    def test_elastic_restore_across_meshes(self, ctx, tmp_path):
        """Checkpoint written under one mesh restores under another."""
        model = build_model(ctx)
        params = materialize_params(model.param_specs(), jax.random.PRNGKey(0))
        mgr = CheckpointManager(str(tmp_path), keep=1)
        mgr.save(params, step=1)

        mesh2 = make_mesh((1,), ("model",))
        from repro.dist.sharding import DEFAULT_RULES, sharding_tree

        sh = sharding_tree(model.param_specs(), DEFAULT_RULES, mesh2)
        restored, step = mgr.restore(params, shardings=sh)
        assert step == 1
        leaf = jax.tree.leaves(restored)[0]
        assert leaf.sharding.mesh.axis_names == ("model",)
        mgr.close()


class TestTrainerFaults:
    def test_crash_restart_resumes_from_checkpoint(self, ctx, tmp_path):
        trainer = make_trainer(ctx, tmp_path, ckpt_every=2, max_failures=2)
        trainer.init_state()
        crashed = []

        def fail_once(step):
            if step == 4 and not crashed:
                crashed.append(step)
                raise RuntimeError("injected node failure")

        hist = trainer.train(data(ctx, 12), 6, fail_hook=fail_once, log=lambda m: None)
        assert crashed == [4]
        assert trainer.step_num == 6
        assert trainer.failures == 1
        assert [h["step"] for h in hist][-1] == 6

    def test_failure_budget_exhaustion_raises(self, ctx, tmp_path):
        trainer = make_trainer(ctx, tmp_path, max_failures=1)
        trainer.init_state()

        def always_fail(step):
            raise RuntimeError("persistent failure")

        with pytest.raises(RuntimeError):
            trainer.train(data(ctx, 8), 4, fail_hook=always_fail, log=lambda m: None)

    def test_remesh_preserves_state(self, ctx, tmp_path):
        trainer = make_trainer(ctx, tmp_path)
        trainer.init_state()
        trainer.train(data(ctx, 3), 2, log=lambda m: None)
        before = jax.tree.map(np.asarray, trainer.state["params"])
        new_mesh = make_mesh((1, 1), ("data", "model"))
        trainer.remesh(ModelContext(ctx.cfg, new_mesh, rules_for(new_mesh)))
        after = jax.tree.map(np.asarray, trainer.state["params"])
        jax.tree.map(np.testing.assert_array_equal, before, after)
        trainer.train(data(ctx, 6)[2:], 4, log=lambda m: None)  # still trains
        assert trainer.step_num == 4


class TestFaultPrimitives:
    def test_heartbeat_lease_lifecycle(self):
        store = Store("hb-test")
        mon = HeartbeatMonitor(store, ttl=0.3)
        mon.register("w0")
        mon.register("w1")
        assert set(mon.live_workers()) == {"w0", "w1"}
        import time

        for _ in range(3):  # w0 keeps beating; w1 goes silent
            time.sleep(0.15)
            mon.heartbeat("w0")
        time.sleep(0.25)
        assert "w1" in mon.dead_workers()
        with pytest.raises(TimeoutError):
            mon.heartbeat("w1")  # dead workers must re-register
        store.close()

    def test_elastic_plan_shrinks_after_loss(self):
        full = elastic_plan(512, model_parallel=16, chips_per_pod=256)
        assert (full.pods, full.data, full.model) == (2, 16, 16)
        degraded = elastic_plan(512 - 96, model_parallel=16, chips_per_pod=256)
        assert degraded.model == 16
        assert degraded.chips <= 512 - 96
        tiny = elastic_plan(48, model_parallel=16)
        assert tiny.data == 2  # 48//16=3 → pow2 floor
        with pytest.raises(ValueError):
            elastic_plan(8, model_parallel=16)

    def test_straggler_policy_decisions(self):
        pol = StragglerPolicy(warn_factor=2.0, redispatch_factor=4.0)
        for _ in range(6):
            assert pol.observe(1.0) is None
        assert pol.observe(2.5) == "warn"
        assert pol.observe(5.0) == "redispatch"
        assert pol.observe(1.1) is None


class TestReshardedCheckpoint:
    """PR 4: leaves saved as axis-0 chunks; restores read per-shard slices."""

    def test_manifest_is_chunked(self, ctx, tmp_path):
        model = build_model(ctx)
        params = materialize_params(model.param_specs(), jax.random.PRNGKey(0))
        mgr = CheckpointManager(str(tmp_path), keep=1, leaf_shards=4)
        mgr.save(params, step=1)
        with open(mgr._manifest_path(1)) as f:
            manifest = json.load(f)
        metas = list(manifest["leaves"].values())
        assert all("keys" in m and "bounds" in m for m in metas)
        multi = [m for m in metas if len(m["keys"]) > 1]
        assert multi  # every axis-0-divisible leaf really is sharded
        for m in metas:
            assert len(m["bounds"]) == len(m["keys"]) + 1
            if m["shape"]:
                assert m["bounds"][-1] == m["shape"][0]
        mgr.close()

    def test_partial_fetch_reads_only_overlapping_chunks(self, tmp_path):
        arr = np.arange(32, dtype=np.float32).reshape(8, 4)
        mgr = CheckpointManager(str(tmp_path), keep=1, leaf_shards=4)
        mgr.save({"w": arr}, step=1)
        meta = json.load(open(mgr._manifest_path(1)))["leaves"]["['w']"]
        assert meta["bounds"] == [0, 2, 4, 6, 8]
        mgr.close()

        cold = CheckpointManager(str(tmp_path), keep=1)  # fresh store, no cache
        before = cold._store.metrics.get_count
        rows = cold._fetch_rows(meta, 2, 4, "w")
        np.testing.assert_array_equal(rows, arr[2:4])
        # rows [2,4) live in exactly one chunk: exactly one channel read
        assert cold._store.metrics.get_count - before == 1
        cold.close()

        cold2 = CheckpointManager(str(tmp_path), keep=1)
        before = cold2._store.metrics.get_count
        rows = cold2._fetch_rows(meta, 3, 7, "w")
        np.testing.assert_array_equal(rows, arr[3:7])
        assert cold2._store.metrics.get_count - before == 3  # 3 overlapping chunks
        cold2.close()

    def test_sharded_restore_via_callback_matches(self, ctx, tmp_path):
        """Restore with shardings goes through make_array_from_callback on
        per-chunk reads and still reproduces every leaf bit-identically."""
        model = build_model(ctx)
        params = materialize_params(model.param_specs(), jax.random.PRNGKey(0))
        mgr = CheckpointManager(str(tmp_path), keep=1, leaf_shards=4)
        mgr.save(params, step=1)

        from repro.dist.sharding import sharding_tree

        sh = sharding_tree(model.param_specs(), ctx.rules, ctx.mesh)
        restored, step = mgr.restore(params, shardings=sh)
        assert step == 1
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
            params, restored,
        )
        mgr.close()

    def test_zero_length_leaf_roundtrips(self, tmp_path):
        arr = np.zeros((0, 4), np.float32)
        mgr = CheckpointManager(str(tmp_path), keep=1, leaf_shards=4)
        mgr.save({"empty": arr}, step=1)
        restored, _ = mgr.restore({"empty": arr})
        assert np.asarray(restored["empty"]).shape == (0, 4)
        mgr.close()

    def test_legacy_whole_leaf_manifest_restores(self, tmp_path):
        """Pre-PR4 manifests (one `key` per leaf) still restore."""
        arr = np.arange(12, dtype=np.float32).reshape(3, 4)
        mgr = CheckpointManager(str(tmp_path), keep=1)
        mgr._store.put(arr, key="legacy-leaf")
        manifest = {
            "step": 9, "time": 0.0,
            "leaves": {"['w']": {"key": "legacy-leaf", "shape": [3, 4],
                                 "dtype": "float32"}},
        }
        with open(mgr._manifest_path(9), "w") as f:
            json.dump(manifest, f)
        restored, step = mgr.restore({"w": arr})
        assert step == 9
        np.testing.assert_array_equal(np.asarray(restored["w"]), arr)
        mgr.close()


class TestDispatchingLoader:
    """PR 4: the `redispatch` grade acts — shards are re-issued to live
    workers, committed exactly once through put_if_absent."""

    def _corpus(self, ctx):
        return SyntheticCorpus(ctx.cfg, 2, 16)

    def test_all_shards_delivered_in_order(self, ctx):
        corpus = self._corpus(ctx)
        loader = DispatchingDataLoader(
            corpus.next_batch, num_steps=6, workers=2, prefetch=2
        )
        got = [extract(p)["tokens"] for p in loader]
        assert len(got) == 6
        for i, toks in enumerate(got):
            np.testing.assert_array_equal(toks, corpus.next_batch(i)["tokens"])
        loader.stop()

    def test_straggler_shard_redispatched_to_other_worker(self, ctx):
        corpus = self._corpus(ctx)
        release = threading.Event()
        hung = []

        def worker_fn(worker, step):
            if step == 5 and not hung:  # first issue of shard 5 wedges
                hung.append(worker)
                release.wait(timeout=60)
            return corpus.next_batch(step)

        policy = StragglerPolicy(
            warn_factor=2.0, redispatch_factor=4.0, window=8, min_samples=3
        )
        loader = DispatchingDataLoader(
            corpus.next_batch, num_steps=8, workers=["dw0", "dw1"],
            policy=policy, worker_fn=worker_fn, prefetch=2,
            supervise_every=0.01, shard_timeout=60.0,
        )
        try:
            got = [extract(p)["tokens"] for p in loader]
            assert len(got) == 8
            np.testing.assert_array_equal(got[5], corpus.next_batch(5)["tokens"])
            stragglers = [
                r for r in loader.redispatches
                if r["step"] == 5 and r["reason"] == "straggler"
            ]
            assert stragglers
            assert stragglers[0]["to"] != hung[0]  # re-issued to the OTHER worker
        finally:
            release.set()
            loader.stop()

    def test_worker_error_shard_redispatched(self, ctx):
        """A worker exception must not strand its shard: the step is
        re-issued immediately and the error is recorded, not swallowed."""
        corpus = self._corpus(ctx)
        blew = []

        def worker_fn(worker, step):
            if step == 2 and not blew:
                blew.append(worker)
                raise RuntimeError("boom")
            return corpus.next_batch(step)

        policy = StragglerPolicy(min_samples=10**6)  # isolate the error path
        loader = DispatchingDataLoader(
            corpus.next_batch, num_steps=5, workers=2, policy=policy,
            worker_fn=worker_fn, prefetch=2, supervise_every=0.01,
            shard_timeout=60.0,
        )
        try:
            got = [extract(p) for p in loader]
            assert len(got) == 5
            np.testing.assert_array_equal(
                got[2]["tokens"], corpus.next_batch(2)["tokens"]
            )
            assert loader.errors and loader.errors[0]["step"] == 2
            assert any(
                r["reason"] == "worker-error" and r["step"] == 2
                for r in loader.redispatches
            )
        finally:
            loader.stop()

    def test_dead_worker_shards_redispatched(self, ctx):
        corpus = self._corpus(ctx)

        class FakeMonitor:
            def __init__(self):
                self.alive = {"dw0", "dw1"}

            def live_workers(self):
                return sorted(self.alive)

        mon = FakeMonitor()
        stall = threading.Event()

        def worker_fn(worker, step):
            if worker == "dw0":
                stall.wait(timeout=60)  # dw0 never finishes anything
            return corpus.next_batch(step)

        # min_samples high: only the death path may trigger re-issues
        policy = StragglerPolicy(min_samples=10**6)
        loader = DispatchingDataLoader(
            corpus.next_batch, num_steps=6, workers=["dw0", "dw1"],
            policy=policy, monitor=mon, worker_fn=worker_fn, prefetch=2,
            supervise_every=0.01, shard_timeout=60.0,
        )
        try:
            loader.start()
            time.sleep(0.1)  # let dw0 pick up a shard, then "kill" it
            mon.alive.discard("dw0")
            got = [extract(p) for p in loader]
            assert len(got) == 6
            dead = [r for r in loader.redispatches if r["reason"] == "dead-worker"]
            assert dead and all(r["from"] == "dw0" and r["to"] == "dw1" for r in dead)
        finally:
            stall.set()
            loader.stop()


class TestPipeline:
    def test_loader_yields_proxies_in_order(self, ctx):
        corpus = SyntheticCorpus(ctx.cfg, 2, 16)
        loader = StreamingDataLoader(corpus.next_batch, num_steps=5, prefetch=2)
        from repro.core.proxy import Proxy, extract

        steps = []
        for p in loader:
            assert isinstance(p, Proxy)
            steps.append(extract(p)["tokens"].shape)
        assert len(steps) == 5
        assert all(s == (2, 16) for s in steps)
