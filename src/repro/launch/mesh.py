"""Production mesh factories + the elastic MeshPlan → Mesh driver.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, while smoke tests and benchmarks must keep seeing 1 device.

:class:`ElasticMeshDriver` (PR 4) closes the fault loop the PR 1 stub left
open: lease membership (``dist.lease``) → :func:`repro.dist.fault.
elastic_plan` → :func:`plan_to_mesh` → ``Trainer.request_remesh``.  The
driver *subscribes* to membership through ``LeaseService.watch`` (one
notification-based ``wait_for_any`` per round, deadline-capped at the next
lease expiry) — never a poll loop — and relies on the ``materialize_params``
determinism invariant: params re-placed on the new mesh are bitwise the
logical arrays the old mesh held.
"""
from __future__ import annotations

import math
import threading
import time

import jax
from jax.sharding import AxisType, Mesh

from repro.dist.fault import MeshPlan, elastic_plan
from repro.dist.lease import LeaseService, MembershipSnapshot
from repro.dist.sharding import AxisRules, DEFAULT_RULES, MULTIPOD_RULES, RULE_PROFILES


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the code shards through
    ``with_sharding_constraint`` and placed inputs, and lets XLA propagate
    the rest (``jax.make_mesh`` defaults to ``Explicit`` axes, under which
    the engine's page-pool scatters are rejected)."""
    return jax.make_mesh(
        shape, axes, devices=devices, axis_types=(AxisType.Auto,) * len(shape)
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single-pod (16 data × 16 model) = 256 chips or 2-pod = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    devices = jax.devices()[: math.prod(shape)]
    return make_mesh(shape, axes, devices=devices)


def rules_for(mesh: Mesh, profile: str = "default") -> AxisRules:
    pod_rules, multipod_rules = RULE_PROFILES[profile]
    return multipod_rules if "pod" in mesh.shape else pod_rules


def make_host_mesh() -> Mesh:
    """1-device mesh for smoke tests / CPU examples (same axis names)."""
    return make_mesh((1, 1), ("data", "model"))


def plan_to_mesh(plan: MeshPlan, *, devices=None) -> Mesh:
    """Realize a :class:`MeshPlan` as a ``jax.Mesh``.

    Uses the plan's ``as_mesh_spec`` (pod axis only when >1); raises when
    the plan wants more devices than the runtime has — an elastic re-plan
    must never silently oversubscribe.
    """
    shape, names = plan.as_mesh_spec()
    devices = list(jax.devices()) if devices is None else list(devices)
    need = math.prod(shape)
    if len(devices) < need:
        raise ValueError(
            f"plan {plan} needs {need} devices; runtime has {len(devices)}"
        )
    return make_mesh(shape, names, devices=devices[:need])


class ElasticMeshDriver:
    """Watch lease membership; re-plan and re-mesh the trainer on change.

    ``trainer`` is duck-typed: anything with ``request_remesh(ctx,
    plan=...)`` (the Trainer applies it at the next step boundary — a
    remesh must not race a running step).  ``mesh_factory(plan)`` defaults
    to :func:`plan_to_mesh`; tests inject a smoke factory that maps any
    plan onto the 1-device mesh (same axis names, so the rules profile
    still switches between pod/multipod resolution).

    Capacity model: each live lease contributes ``chips_per_worker`` chips
    (a worker is a host owning a fixed slice of the pod); ``elastic_plan``
    pins model parallelism and degrades data parallelism to a power of two.
    """

    def __init__(
        self,
        leases: LeaseService,
        trainer,
        cfg,
        *,
        chips_per_worker: int,
        model_parallel: int,
        chips_per_pod: int = 256,
        profile: str = "default",
        mesh_factory=None,
        use_kernels: bool = False,
    ):
        self.leases = leases
        self.trainer = trainer
        self.cfg = cfg
        self.chips_per_worker = chips_per_worker
        self.model_parallel = model_parallel
        self.chips_per_pod = chips_per_pod
        self.profile = profile
        self.mesh_factory = mesh_factory or plan_to_mesh
        self.use_kernels = use_kernels
        self.events: list[dict] = []
        self.snap: MembershipSnapshot = leases.snapshot()
        self.plan: MeshPlan | None = self._plan_for(len(self.snap.live))
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()

    def _plan_for(self, live_workers: int) -> MeshPlan | None:
        try:
            return elastic_plan(
                live_workers * self.chips_per_worker,
                model_parallel=self.model_parallel,
                chips_per_pod=self.chips_per_pod,
            )
        except ValueError:
            return None  # below one model-parallel group: no viable mesh

    def _context_for(self, plan: MeshPlan):
        from repro.models.layers import ModelContext

        mesh = self.mesh_factory(plan)
        return ModelContext(
            self.cfg, mesh, rules_for(mesh, self.profile), self.use_kernels
        )

    def check(self, timeout: float | None = 1.0) -> MeshPlan | None:
        """One subscription round: block until membership may have changed
        (or ``timeout``), re-plan, and request a remesh when the plan moved.

        Returns the new plan when a remesh was requested, else ``None``.
        """
        snap = self.leases.watch(self.snap, timeout=timeout)
        if snap == self.snap:
            return None
        self.snap = snap
        plan = self._plan_for(len(snap.live))
        if plan is None:
            self.events.append(
                {"kind": "no-capacity", "live": list(snap.live), "t": time.time()}
            )
            return None
        if plan == self.plan:
            return None
        old, self.plan = self.plan, plan
        self.events.append(
            {"kind": "replan", "live": list(snap.live), "from": str(old),
             "to": str(plan), "t": time.time()}
        )
        self.trainer.request_remesh(self._context_for(plan), plan=plan)
        return plan

    # -- background loop ----------------------------------------------------------
    def run(self, stop: threading.Event | None = None, poll: float = 1.0) -> None:
        stop = stop or self._stop
        while not stop.is_set():
            try:
                self.check(timeout=poll)
            except Exception as e:  # noqa: BLE001 - the watch must survive
                # e.g. plan_to_mesh on a box with too few devices: record
                # and keep watching — a dead watch thread is silent loss of
                # all fault tolerance, strictly worse than a failed remesh
                self.events.append(
                    {"kind": "error", "error": repr(e), "t": time.time()}
                )
                # don't hot-loop on a persistent failure
                time.sleep(poll)  # proxylint: disable=no-sleep-poll

    def start(self, poll: float = 1.0) -> None:
        self._stop.clear()
        self._thread = threading.Thread(
            target=self.run, kwargs={"poll": poll}, daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
