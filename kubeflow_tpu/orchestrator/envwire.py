"""Per-worker environment construction — the ``setPodEnv`` analog.

Where the reference's controllers write ``MASTER_ADDR/RANK/WORLD_SIZE``
(PyTorchJob), ``TF_CONFIG`` (TFJob) or hostfiles (MPIJob), the JAXJob
control plane writes the ``jax.distributed`` contract consumed by
``kubeflow_tpu.core.distributed`` plus job-identity vars (SURVEY.md §2.7
"c10d TCPStore" row; upstream analog [training-operator]
pkg/controller.v1/pytorch/envvar.go — UNVERIFIED, SURVEY.md §0).

Two wiring modes:

- ``tpu``:     the worker inherits the host's TPU env (real chips). A chip
  belongs to one process, and no visible-device restriction is wired, so
  this mode takes ONE worker per host (:func:`check_gang_fits`).
- ``cpu_sim``: workers get JAX_PLATFORMS=cpu and a virtual device count —
  the gloo-on-kind analog (SURVEY.md §4) for exercising real cross-process
  collectives on one host.
"""

from __future__ import annotations

import dataclasses
import os
import socket

from kubeflow_tpu.core import distributed as dist
from kubeflow_tpu.orchestrator.spec import JobSpec

ENV_JOB_NAME = "KFT_JOB_NAME"
ENV_JOB_UID = "KFT_JOB_UID"
ENV_NAMESPACE = "KFT_NAMESPACE"
ENV_REPLICA_TYPE = "KFT_REPLICA_TYPE"
ENV_REPLICA_INDEX = "KFT_REPLICA_INDEX"
ENV_WORKDIR = "KFT_WORKDIR"
ENV_ATTEMPT = "KFT_ATTEMPT"


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclasses.dataclass(frozen=True)
class WiringConfig:
    """How a job's gang is wired on this host."""

    platform: str = "cpu_sim"  # "cpu_sim" | "tpu"
    devices_per_worker: int = 1
    coordinator_host: str = "127.0.0.1"

    def __post_init__(self) -> None:
        if self.platform not in ("cpu_sim", "tpu"):
            raise ValueError(f"unknown platform {self.platform!r}")


def check_gang_fits(wiring: WiringConfig, world: int) -> None:
    """``platform="tpu"`` hands every child the whole host's chips; a
    second child would fail or hang at backend init, so refuse it here."""
    if wiring.platform == "tpu" and world > 1:
        raise ValueError(
            f'platform="tpu" runs one worker per host (it owns every local '
            f"chip); this gang has {world} processes — use one worker that "
            f'drives all chips, or platform="cpu_sim"'
        )


def build_worker_env(
    job: JobSpec,
    rtype: str,
    index: int,
    *,
    coordinator_port: int,
    wiring: WiringConfig,
    workdir: str,
    attempt: int,
    service_ports: dict[str, int] | None = None,
    base_env: dict[str, str] | None = None,
) -> dict[str, str]:
    """Full child environment for one gang member."""
    from kubeflow_tpu.orchestrator import kinds

    env = dict(os.environ if base_env is None else base_env)
    env.update(job.replicas[rtype].env)
    # kind-specific rendezvous contract (MASTER_ADDR / TF_CONFIG / DMLC_* /
    # hostfile / PADDLE_*) — the per-kind controllers' env wiring, unified.
    env.update(
        kinds.kind_env(
            job,
            rtype,
            index,
            host=wiring.coordinator_host,
            service_ports=service_ports or {},
            workdir=workdir,
        )
    )

    ranks = job.global_ranks()
    rank = ranks[(rtype, index)]
    world = job.total_replicas

    env.update(
        {
            dist.ENV_COORDINATOR_ADDRESS: f"{wiring.coordinator_host}:{coordinator_port}",
            dist.ENV_NUM_PROCESSES: str(world),
            dist.ENV_PROCESS_ID: str(rank),
            ENV_JOB_NAME: job.name,
            ENV_JOB_UID: job.uid,
            ENV_NAMESPACE: job.namespace,
            ENV_REPLICA_TYPE: rtype,
            ENV_REPLICA_INDEX: str(index),
            ENV_WORKDIR: workdir,
            ENV_ATTEMPT: str(attempt),
            # GKE-parity topology surface (SURVEY.md §5.8)
            dist.ENV_TPU_WORKER_ID: str(rank),
            dist.ENV_TPU_WORKER_HOSTNAMES: ",".join(
                [wiring.coordinator_host] * world
            ),
            "PYTHONUNBUFFERED": "1",
        }
    )

    if wiring.platform == "cpu_sim":
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        flags = " ".join(
            p
            for p in flags.split()
            if not p.startswith("--xla_force_host_platform_device_count")
        )
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{wiring.devices_per_worker}"
        ).strip()
    return env
