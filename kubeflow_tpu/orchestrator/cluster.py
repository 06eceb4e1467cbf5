"""LocalCluster: the whole control plane wired together on one host.

store + gang scheduler + launcher + controller loop — the single-binary
analog of apiserver + scheduler + kubelet + training-operator for this
clusterless dev environment (SURVEY.md §7 env constraints). The controller
loop is event-driven (store watches) with a periodic resync for time-based
policies (deadlines, TTL, restart backoff), like controller-runtime's
informer resync.
"""

from __future__ import annotations

import logging
import tempfile
import threading
import time

from kubeflow_tpu.obs import names, prom
from kubeflow_tpu.orchestrator.envwire import WiringConfig, check_gang_fits
from kubeflow_tpu.orchestrator.gang import GangScheduler
from kubeflow_tpu.orchestrator.launcher import ProcessLauncher
from kubeflow_tpu.orchestrator.reconciler import JobController, JobObject
from kubeflow_tpu.orchestrator.resources import Fleet
from kubeflow_tpu.orchestrator.spec import JobSpec, JobStatus
from kubeflow_tpu.orchestrator.store import ObjectStore
from kubeflow_tpu.orchestrator.supervisor import HeartbeatSupervisor
from kubeflow_tpu.orchestrator.webhooks import AdmissionChain

logger = logging.getLogger(__name__)

SYNC_SECONDS = prom.REGISTRY.histogram(
    names.RECONCILE_SECONDS, "controller sync_all wall time"
)
JOBS_BY_PHASE = prom.REGISTRY.gauge(
    names.JOBS_BY_PHASE, "jobs currently in the store by phase",
    labels=("phase",),
)


class LocalCluster:
    def __init__(
        self,
        fleet: Fleet | None = None,
        wiring: WiringConfig | None = None,
        *,
        base_dir: str | None = None,
        persist_path: str | None = None,
        resync_period: float = 0.1,
        restart_backoff_base: float = 1.0,
        admission: "AdmissionChain | None" = None,
        queues=None,
        preemption_grace_seconds: float = 5.0,
    ):
        self.fleet = fleet or Fleet.single_host(chips=8)
        self.wiring = wiring or WiringConfig(platform="cpu_sim")
        self.base_dir = base_dir or tempfile.mkdtemp(prefix="kft-cluster-")
        if persist_path:
            # etcd analog: jobs survive a control-plane restart. Worker
            # records deliberately do NOT — they describe live processes of
            # the dead incarnation; the reconciler re-forms each unfinished
            # job's gang from desired state (training resumes from its own
            # checkpoints, same shape as elastic resize).
            from kubeflow_tpu.orchestrator.store import SqliteObjectStore

            from kubeflow_tpu.orchestrator.spec import JobConditionType as CT

            self.jobs = SqliteObjectStore("jobs", persist_path)
            for uid, job in self.jobs.list():
                if not job.status.finished:
                    job.coordinator_port = 0
                    job.service_ports = {}
                    job.status.push(
                        CT.RESTARTING,
                        reason="ControllerRestart",
                        message="control plane restarted; re-forming gang",
                    )
                    self.jobs.checkpoint(uid)
        else:
            self.jobs = ObjectStore("jobs")
        self.workers = ObjectStore("workers")
        if queues is not None:
            # multi-tenant quota admission (the Kueue analog): queues may
            # be a QueueConfig or an iterable of queue specs/manifests
            from kubeflow_tpu.sched import QueueConfig, QuotaScheduler

            config = (
                queues
                if isinstance(queues, QueueConfig)
                else QueueConfig.from_specs(queues)
            )
            self.scheduler: GangScheduler = QuotaScheduler(
                self.fleet,
                config,
                preemption_grace_seconds=preemption_grace_seconds,
            )
        else:
            self.scheduler = GangScheduler(self.fleet)
        self.launcher = ProcessLauncher(self.workers, self.base_dir)
        self.supervisor = HeartbeatSupervisor(
            self.jobs, self.workers, self.launcher
        )
        self.controller = JobController(
            self.jobs,
            self.workers,
            self.scheduler,
            self.launcher,
            self.wiring,
            restart_backoff_base=restart_backoff_base,
            supervisor=self.supervisor,
        )
        self.admission = admission or AdmissionChain()
        if queues is not None:
            from kubeflow_tpu.orchestrator.webhooks import (
                queue_membership_validator,
            )

            self.admission.add_validator(
                queue_membership_validator(self.scheduler)
            )
        # admission validators read live state (quota usage); serializing
        # admit+create closes the check-then-act window between concurrent
        # submits (concurrent deletes only free capacity, the safe direction)
        self._submit_lock = threading.Lock()
        self._resync = resync_period
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._watches = []

    # ------------------------------------------------------------------ #

    def start(self) -> "LocalCluster":
        if self._thread is not None:
            return self
        for store in (self.jobs, self.workers):
            watch = store.watch()
            self._watches.append(watch)
            threading.Thread(
                target=self._pump, args=(watch,), daemon=True
            ).start()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def _pump(self, watch) -> None:
        for _ in watch:
            self._wake.set()

    def _loop(self) -> None:
        while not self._stop.is_set():
            self._wake.wait(timeout=self._resync)
            self._wake.clear()
            if self._stop.is_set():
                return
            with SYNC_SECONDS.time():
                self.supervisor.check()
                self.controller.sync_all()
            phases: dict[str, int] = {}
            for _, job in self.jobs.list():
                phases[job.status.phase] = phases.get(job.status.phase, 0) + 1
            # "Unknown" = submitted but not yet reconciled (no conditions)
            for phase in ("Unknown", "Created", "Queued", "Running",
                          "Restarting", "Succeeded", "Failed"):
                JOBS_BY_PHASE.labels(phase=phase).set(phases.get(phase, 0))

    def shutdown(self) -> None:
        self._stop.set()
        self._wake.set()
        for w in self._watches:
            w.stop()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        self.launcher.shutdown()
        close = getattr(self.scheduler, "close", None)
        if close is not None:  # QuotaScheduler: drop its /metrics collector
            close()

    def __enter__(self) -> "LocalCluster":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # -- job API (what the SDK client calls) --------------------------- #

    def submit(self, spec: JobSpec) -> str:
        check_gang_fits(self.wiring, spec.total_replicas)
        with self._submit_lock:
            spec = self.admission.admit(spec)
            self.jobs.create(spec.uid, JobObject(spec=spec))
        self._wake.set()
        return spec.uid

    def get(self, uid: str) -> JobObject | None:
        return self.jobs.get(uid)

    def find(self, name: str, namespace: str = "default") -> JobObject | None:
        for _, job in self.jobs.list():
            if job.spec.name == name and job.spec.namespace == namespace:
                return job
        return None

    def status(self, uid: str) -> JobStatus | None:
        job = self.jobs.get(uid)
        return job.status if job else None

    def delete(self, uid: str) -> None:
        job: JobObject | None = self.jobs.get(uid)
        if job is None:
            return
        job.deletion_requested = True
        self.jobs.update(uid, job)
        self._wake.set()

    def wait(
        self,
        uid: str,
        timeout: float = 300.0,
        *,
        poll: float = 0.05,
    ) -> JobStatus:
        """Block until the job reaches a terminal condition (or is deleted)."""
        deadline = time.time() + timeout
        last: JobStatus | None = None
        while time.time() < deadline:
            job = self.jobs.get(uid)
            if job is None:
                if last is not None:
                    return last  # TTL'd away after finishing
                raise KeyError(f"job {uid} not found")
            last = job.status
            if job.status.finished:
                return job.status
            time.sleep(poll)
        raise TimeoutError(
            f"job {uid} not finished after {timeout}s "
            f"(phase {last.phase if last else 'Unknown'})"
        )

    def scale(self, uid: str, replicas: int) -> int:
        """Resize an elastic job's scalable group (HPA analog); the gang
        re-forms at the new size and resumes from checkpoint."""
        check_gang_fits(self.wiring, replicas)
        applied = self.controller.scale(uid, replicas)
        self._wake.set()
        return applied

    def logs(self, uid: str, rtype: str, index: int, attempt: int | None = None) -> str:
        """Concatenated (or single-attempt) worker logs."""
        w = self.workers.get(f"{uid}/{rtype}-{index}")
        attempts = (
            [attempt]
            if attempt is not None
            else range((w.restarts if w else 0) + 1)
        )
        chunks = []
        for a in attempts:
            p = self.launcher.log_path(uid, rtype, index, a)
            if p.exists():
                chunks.append(p.read_text(errors="replace"))
        return "".join(chunks)
