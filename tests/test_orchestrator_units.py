"""Unit tests: spec semantics, store watches, fleet claims, gang queueing.

The table-driven-unit-test tier of the reference's strategy (SURVEY.md §4
"Go unit tests": reconcile math, env construction, gang PodGroup logic —
tested in isolation, no processes).
"""

import sys

import pytest

from kubeflow_tpu.orchestrator import envwire
from kubeflow_tpu.orchestrator.gang import GangScheduler, PodGroup
from kubeflow_tpu.orchestrator.resources import Fleet, Slice, parse_topology, topology_chips
from kubeflow_tpu.orchestrator.spec import (
    JobSpec,
    ReplicaSpec,
    RestartPolicy,
    TPURequest,
)
from kubeflow_tpu.orchestrator.store import ObjectStore

PY = sys.executable


# --------------------------- spec ------------------------------------- #

@pytest.mark.parametrize(
    "policy,code,expect",
    [
        (RestartPolicy.ALWAYS, 0, True),
        (RestartPolicy.ALWAYS, 1, True),
        (RestartPolicy.ON_FAILURE, 0, False),
        (RestartPolicy.ON_FAILURE, 1, True),
        (RestartPolicy.NEVER, 1, False),
        (RestartPolicy.EXIT_CODE, 1, False),      # app error: permanent
        (RestartPolicy.EXIT_CODE, 127, False),
        (RestartPolicy.EXIT_CODE, 137, True),     # SIGKILL: infra, retry
        (RestartPolicy.EXIT_CODE, 139, True),     # SIGSEGV
    ],
)
def test_restart_policy_table(policy, code, expect):
    assert policy.should_restart(code) is expect


def test_jobspec_validation():
    with pytest.raises(ValueError):
        JobSpec(name="x", replicas={})
    with pytest.raises(ValueError):
        JobSpec(name="x", replicas={"w": ReplicaSpec(replicas=0, command=("a",))})
    with pytest.raises(ValueError):
        JobSpec(name="x", replicas={"w": ReplicaSpec(replicas=1)})


def test_rank_ordering_master_first():
    job = JobSpec(
        name="j",
        replicas={
            "worker": ReplicaSpec(replicas=2, command=("w",)),
            "master": ReplicaSpec(replicas=1, command=("m",)),
        },
    )
    ranks = job.global_ranks()
    assert ranks[("master", 0)] == 0
    assert ranks[("worker", 0)] == 1
    assert ranks[("worker", 1)] == 2
    assert job.total_replicas == 3


def test_jobspec_dict_roundtrip():
    job = JobSpec(
        name="j",
        replicas={
            "worker": ReplicaSpec(
                replicas=2,
                command=(PY, "-c", "pass"),
                env={"A": "1"},
                restart_policy=RestartPolicy.EXIT_CODE,
                tpu=TPURequest(chips=4, topology="2x2"),
            )
        },
    )
    clone = JobSpec.from_dict(job.to_dict())
    assert clone.to_dict() == job.to_dict()
    assert clone.replicas["worker"].tpu.topology == "2x2"


def test_env_wiring():
    job = JobSpec(
        name="j",
        replicas={
            "master": ReplicaSpec(replicas=1, command=("m",), env={"USER_VAR": "u"}),
            "worker": ReplicaSpec(replicas=2, command=("w",)),
        },
    )
    env = envwire.build_worker_env(
        job, "worker", 1,
        coordinator_port=1234,
        wiring=envwire.WiringConfig(platform="cpu_sim", devices_per_worker=2),
        workdir="/tmp/w", attempt=3, base_env={"XLA_FLAGS": "--xla_foo=1"},
    )
    assert env["JAX_COORDINATOR_ADDRESS"] == "127.0.0.1:1234"
    assert env["JAX_NUM_PROCESSES"] == "3"
    assert env["JAX_PROCESS_ID"] == "2"  # master=0, worker-0=1, worker-1=2
    assert env["KFT_REPLICA_TYPE"] == "worker"
    assert env["KFT_ATTEMPT"] == "3"
    assert env["JAX_PLATFORMS"] == "cpu"
    # the caller's other XLA flags survive beside the device count
    assert env["XLA_FLAGS"].split() == [
        "--xla_foo=1", "--xla_force_host_platform_device_count=2"
    ]
    master_env = envwire.build_worker_env(
        job, "master", 0, coordinator_port=1234,
        wiring=envwire.WiringConfig(), workdir="/tmp/w", attempt=0,
    )
    assert master_env["USER_VAR"] == "u"
    assert master_env["JAX_PROCESS_ID"] == "0"


def test_tpu_wiring_is_one_worker_per_host():
    """A chip belongs to one process and the launcher wires no
    visible-device restriction: a second TPU child would hang at backend
    init, so the gang is refused at submit instead."""
    tpu = envwire.WiringConfig(platform="tpu")
    envwire.check_gang_fits(tpu, 1)
    envwire.check_gang_fits(envwire.WiringConfig(platform="cpu_sim"), 4)
    with pytest.raises(ValueError, match="one worker per host"):
        envwire.check_gang_fits(tpu, 2)


# --------------------------- store ------------------------------------ #

def test_store_crud_and_watch():
    s = ObjectStore("t")
    s.create("a", {"v": 1})
    with pytest.raises(KeyError):
        s.create("a", {})
    watch = s.watch()
    ev = watch.poll(timeout=1)
    assert ev.kind == "ADDED" and ev.key == "a"  # replay of current state
    s.update("a", {"v": 2})
    assert watch.poll(timeout=1).kind == "MODIFIED"
    s.mutate("a", lambda o: o.update(v=3))
    assert s.get("a")["v"] == 3
    s.delete("a")
    ev = watch.poll(timeout=1)  # mutate event
    ev = watch.poll(timeout=1)  # delete event
    assert ev.kind == "DELETED"
    watch.stop()


# --------------------------- fleet ------------------------------------ #

def test_parse_topology():
    assert parse_topology("4x4") == (4, 4)
    assert topology_chips("2x4") == 8
    with pytest.raises(ValueError):
        parse_topology("4xx")


def test_fleet_gang_all_or_nothing():
    fleet = Fleet.homogeneous(2, "2x2")  # 2 slices x 4 chips
    assert fleet.total_chips() == 8
    # gang of 3x2 chips fits (4+2 on one slice, 2... best fit packs)
    claims = fleet.claim_gang([(2, None, "v5e")] * 3)
    assert claims is not None and fleet.free_chips() == 2
    # next gang of 2x2 chips: only 2 free → all-or-nothing refuses
    assert fleet.claim_gang([(2, None, "v5e")] * 2) is None
    assert fleet.free_chips() == 2  # nothing leaked
    fleet.release(claims)
    assert fleet.free_chips() == 8


def test_fleet_whole_slice_topology_claim():
    fleet = Fleet.homogeneous(2, "2x2")
    # partial claim dirties slice-0 (best-fit will pick one slice)
    partial = fleet.claim_gang([(1, None, "v5e")])
    # whole-slice claim must land on the untouched slice
    whole = fleet.claim_gang([(0, "2x2", "v5e")])
    assert whole is not None
    assert whole[0].slice_id != partial[0].slice_id
    assert whole[0].chips == 4
    # no second clean slice left
    assert fleet.claim_gang([(0, "2x2", "v5e")]) is None


def test_fleet_generation_mismatch():
    fleet = Fleet.homogeneous(1, "2x2", generation="v5e")
    assert fleet.claim_gang([(1, None, "v4")]) is None


def test_slice_loss_simulation():
    fleet = Fleet.homogeneous(2, "2x2")
    fleet.remove_slice("slice-0")
    assert fleet.total_chips() == 4


# --------------------------- gang scheduler ---------------------------- #

def _group(uid, n_chips, n_members=1, **kw):
    return PodGroup(
        job_uid=uid,
        requests=[(f"{uid}/w-{i}", n_chips, None, "v5e") for i in range(n_members)],
        **kw,
    )


def test_gang_priority_then_fifo():
    sched = GangScheduler(Fleet.homogeneous(1, "2x2"))
    sched.enqueue(_group("low", 4, priority=0))
    sched.enqueue(_group("high", 4, priority=5))
    admitted = sched.try_schedule()
    assert [g.job_uid for g in admitted] == ["high"]
    assert sched.claims_for("high") is not None
    assert sched.claims_for("low") is None
    sched.cancel("high")  # releases claims
    assert [g.job_uid for g in sched.try_schedule()] == ["low"]


def test_gang_head_of_line_blocks_queue():
    sched = GangScheduler(Fleet.homogeneous(1, "2x2"))
    sched.enqueue(_group("big", 4, n_members=2))   # needs 8, can't fit
    sched.enqueue(_group("small", 1))
    assert sched.try_schedule() == []  # small must NOT jump the blocked head
    # ...but a different queue is independent
    sched.enqueue(_group("other", 1, queue="q2"))
    assert [g.job_uid for g in sched.try_schedule()] == ["other"]


def test_gang_timeout():
    sched = GangScheduler(Fleet.homogeneous(1, "1x1"))
    sched.enqueue(_group("imposs", 99, timeout_seconds=0.0))
    assert sched.try_schedule() == []
    timed = sched.timed_out()
    assert [g.job_uid for g in timed] == ["imposs"]
    assert sched.pending_count() == 0


# ----------------------- failure-policy mechanics ---------------------- #


def test_fleet_slice_loss_visibility_and_release_tolerance():
    fleet = Fleet.homogeneous(2, "2x2")
    claims = fleet.claim_gang([(4, None, "v5e")])
    assert claims is not None
    sid = claims[0].slice_id
    assert fleet.has_slice(sid)
    fleet.remove_slice(sid)
    assert not fleet.has_slice(sid)
    # releasing claims against a lost slice must be a no-op, not a crash
    fleet.release(claims)
    assert fleet.free_chips() == 4  # only the surviving slice counts


def test_active_deadline_expiry_drives_failed_condition(tmp_path):
    """RunPolicy.activeDeadlineSeconds enforcement, driven directly
    through the reconciler with a fabricated start time — no wall-clock
    waiting on the deadline itself."""
    import time

    from kubeflow_tpu.orchestrator.cluster import LocalCluster
    from kubeflow_tpu.orchestrator.spec import RunPolicy, WorkerPhase

    cluster = LocalCluster(base_dir=str(tmp_path))  # NOT started: we sync
    job = JobSpec(
        name="deadline",
        replicas={
            "worker": ReplicaSpec(
                replicas=1,
                command=(PY, "-c", "import time; time.sleep(60)"),
                tpu=TPURequest(chips=1),
            )
        },
        run_policy=RunPolicy(active_deadline_seconds=5.0),
    )
    uid = cluster.submit(job)
    deadline = time.time() + 10
    while time.time() < deadline:
        cluster.controller.sync_all()
        st = cluster.status(uid)
        if st is not None and st.start_time is not None:
            break
        time.sleep(0.02)
    assert cluster.status(uid).start_time is not None

    # job "has been running" longer than the deadline: next sync fails it
    def _age(j):
        j.status.start_time = time.time() - 6.0

    cluster.jobs.mutate(uid, _age)
    cluster.controller.sync_job(uid)
    st = cluster.status(uid)
    assert st.phase == "Failed"
    assert st.condition().reason == "DeadlineExceeded"
    # cleanPodPolicy killed the sleeper
    deadline = time.time() + 10
    while time.time() < deadline:
        if not any(
            cluster.launcher.alive(k)
            for k, _ in cluster.workers.list(prefix=f"{uid}/")
        ):
            break
        time.sleep(0.02)
    for k, _w in cluster.workers.list(prefix=f"{uid}/"):
        assert not cluster.launcher.alive(k)
    cluster.launcher.shutdown()


def test_reconciler_requeues_gang_on_slice_loss(tmp_path):
    """The reconcile-level slice-loss contract, synchronously: lost
    placement ⇒ RESTARTING/SliceLost, claims released, workers reset to
    PENDING at attempt 1, and NO restart/backoff budget burned."""
    import time

    from kubeflow_tpu.orchestrator.cluster import LocalCluster
    from kubeflow_tpu.orchestrator.spec import (
        JobConditionType as CT, WorkerPhase,
    )

    cluster = LocalCluster(base_dir=str(tmp_path))
    job = JobSpec(
        name="lost-slice",
        replicas={
            "worker": ReplicaSpec(
                replicas=1,
                command=(PY, "-c", "import time; time.sleep(60)"),
                tpu=TPURequest(chips=1),
            )
        },
    )
    uid = cluster.submit(job)
    deadline = time.time() + 10
    while time.time() < deadline:
        cluster.controller.sync_all()
        ws = cluster.workers.list(prefix=f"{uid}/")
        if ws and all(w.phase is WorkerPhase.RUNNING for _, w in ws):
            break
        time.sleep(0.02)
    [(key, w)] = cluster.workers.list(prefix=f"{uid}/")
    assert w.slice_id is not None
    cluster.fleet.remove_slice(w.slice_id)

    cluster.controller.sync_job(uid)
    st = cluster.status(uid)
    restarting = [c for c in st.conditions if c.type is CT.RESTARTING]
    assert restarting and restarting[0].reason == "SliceLost"
    assert st.restart_count == 0  # infra loss burns no backoff budget
    [(key, w)] = cluster.workers.list(prefix=f"{uid}/")
    assert w.phase is WorkerPhase.PENDING
    assert w.restarts == 1 and w.slice_id is None
    assert cluster.scheduler.claims_for(uid) is None
    # no capacity left: the gang queues instead of failing
    cluster.controller.sync_job(uid)
    st = cluster.status(uid)
    assert any(c.type is CT.QUEUED and c.status for c in st.conditions)
    cluster.launcher.shutdown()
