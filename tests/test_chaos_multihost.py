"""Multi-host chaos suite (PR 4): kill a heartbeating worker subprocess
mid-run and prove the whole fault path fires —

    lease expiry → elastic_plan → MeshPlan → Trainer.remesh → training
    resumes → a checkpoint written *before* the mesh change restores
    bit-identically *after* it, through resharded per-chunk leaves.

Worker subprocesses are real interpreters heartbeating over a
``FileConnector`` (the cross-process mediated channel); the parent runs the
monitor, the ``ElasticMeshDriver`` watch thread, and the trainer.  On this
1-device box the mesh factory maps every plan onto a 1-device mesh *with
the plan's axis character* (pod axis present ⇔ multi-pod plan), so the
remesh really swaps rules profiles, re-jits, and re-device_puts — the same
code path a 512-chip deployment takes, scaled down.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import FileConnector, Store
from repro.data.pipeline import SyntheticCorpus
from repro.dist.fault import MeshPlan
from repro.dist.lease import LeaseService
from repro.launch.mesh import ElasticMeshDriver, make_mesh, rules_for
from repro.models.layers import ModelContext
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def _subprocess_env():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _wait_until(predicate, timeout, what):
    deadline = time.time() + timeout
    while not predicate():
        assert time.time() < deadline, f"timed out waiting for {what}"
        time.sleep(0.05)


# A chip host: registers its lease and heartbeats forever (until SIGKILL).
# An expiry (e.g. a long stall) re-registers — the lease protocol's
# recovery path; a fencing loss is fatal (another incarnation owns the name).
_CHAOS_WORKER = """
import sys, time
from repro.core import FileConnector, Store
from repro.dist.lease import LeaseService, LeaseExpired, LeaseLost

directory, name, ttl = sys.argv[1], sys.argv[2], float(sys.argv[3])
svc = LeaseService(
    Store(f"chaos-w-{name}", FileConnector(directory), register=False), ttl=ttl
)
svc.register(name)
while True:
    time.sleep(ttl / 5)
    try:
        svc.renew(name)
    except LeaseExpired:
        svc.register(name)
    except LeaseLost:
        sys.exit(3)
"""


def _smoke_mesh(plan: MeshPlan):
    """Map any MeshPlan onto this box's 1 device, keeping the plan's axis
    character so rules_for still switches pod/multipod resolution."""
    if plan.pods > 1:
        return make_mesh((1, 1, 1), ("pod", "data", "model"))
    return make_mesh((1, 1), ("data", "model"))


TTL = 2.0  # generous: a CPU-share-throttled box must not flap healthy leases


@pytest.mark.multiproc(timeout=480)
class TestChaos:
    def test_worker_death_remesh_and_resharded_restore(self, tmp_path):
        lease_dir = str(tmp_path / "leases")
        monitor = LeaseService(
            Store("chaos-mon", FileConnector(lease_dir), register=False), ttl=TTL
        )
        procs = {
            name: subprocess.Popen(
                [sys.executable, "-c", _CHAOS_WORKER, lease_dir, name, str(TTL)],
                env=_subprocess_env(),
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            )
            for name in ("hostA", "hostB")
        }
        driver = None
        try:
            _wait_until(
                lambda: monitor.live() == ["hostA", "hostB"], 30, "both hosts live"
            )

            cfg = get_smoke_config("smollm-135m")
            mesh0 = _smoke_mesh(MeshPlan(2, 16, 16))
            ctx = ModelContext(cfg, mesh0, rules_for(mesh0))
            tc = TrainerConfig(
                opt=AdamWConfig(lr=1e-3, warmup_steps=2),
                ckpt_every=100,  # only the end-of-train saves matter here
                ckpt_dir=str(tmp_path / "ckpt"),
                log_every=10**6,
            )
            trainer = Trainer(ctx, tc)
            trainer.init_state()
            # 2 live hosts × 256 chips → the full 2-pod 512-chip plan
            driver = ElasticMeshDriver(
                monitor, trainer, cfg,
                chips_per_worker=256, model_parallel=16, chips_per_pod=256,
                mesh_factory=_smoke_mesh,
            )
            assert driver.plan == MeshPlan(2, 16, 16)
            assert "pod" in trainer.ctx.mesh.shape
            driver.start(poll=0.25)

            corpus = SyntheticCorpus(cfg, 2, 32)
            batches = [corpus.next_batch(i) for i in range(12)]
            # phase 1: train on the full mesh; train() checkpoints step 6
            trainer.train(batches[:6], 6, log=lambda m: None)
            assert trainer.step_num == 6
            pre = jax.tree.map(lambda x: np.array(x, copy=True), trainer.state)

            # chaos: SIGKILL a heartbeating host mid-run
            procs["hostB"].kill()
            procs["hostB"].wait(timeout=30)
            _wait_until(lambda: "hostB" in monitor.dead(), 30, "lease expiry")
            _wait_until(
                lambda: trainer._pending_remesh is not None, 30, "remesh request"
            )

            # phase 2: training resumes; the remesh applies at the boundary
            trainer.train(batches[6:], 12, log=lambda m: None)
            assert trainer.step_num == 12
        finally:
            if driver is not None:
                driver.stop()
            for p in procs.values():
                if p.poll() is None:
                    p.kill()
                p.communicate(timeout=30)

        # the degraded plan dropped the dead pod, model parallelism pinned
        assert driver.plan == MeshPlan(1, 16, 16)
        replans = [e for e in driver.events if e["kind"] == "replan"]
        assert replans and replans[-1]["to"] == "data:16xmodel:16"
        assert trainer.remeshes
        assert trainer.remeshes[-1]["mesh_axes"] == ("data", "model")
        assert "pod" not in trainer.ctx.mesh.shape

        # the step-6 checkpoint (written on the 2-pod mesh) restores
        # bit-identically under the post-change mesh, via resharded leaves
        restored, step = trainer.ckpt.restore(
            trainer._abstract_state(), step=6,
            shardings=trainer.bundle.state_shardings,
        )
        assert step == 6
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            pre, restored,
        )
        with open(os.path.join(str(tmp_path / "ckpt"), "manifest-6.json")) as f:
            manifest = json.load(f)
        leaves = manifest["leaves"].values()
        assert all("keys" in m for m in leaves)  # per-shard slices, no
        assert any(len(m["keys"]) > 1 for m in leaves)  # whole-leaf blobs
