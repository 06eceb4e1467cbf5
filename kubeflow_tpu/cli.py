"""``kft`` — the unified command line for the framework.

Reference analogs (SURVEY.md §2 — UNVERIFIED, mount empty, §0): ``kubectl
apply -k`` + the training-operator kubectl plugin, the ``kfp`` CLI, and the
KServe container entrypoint. One binary because the runtime is one process:
the same manifests the Python SDKs accept are accepted here, so
``kft run -f job.yaml`` is the CLI spelling of ``kubectl apply -f`` +
``kubectl wait --for=condition=Succeeded``.

Subcommands:

- ``kft build <dir>``  — resolve a kustomize-style overlay to YAML
  (delegates to `platform.manifests.build`; same output as its module CLI).
- ``kft run -f <path>``— submit every Job/Experiment manifest in a file or
  overlay dir to an in-process LocalCluster, wait for terminal conditions,
  stream failure logs, exit 0 iff everything Succeeded.
- ``kft jobs submit -f <path>`` — ``kft run`` with scheduling overrides:
  ``--queue``/``--priority`` plumb into ``SchedulingPolicy``; an unknown
  LocalQueue is rejected at submit time with a clear error.
- ``kft queues list/show`` — quota queues (Kueue ClusterQueue analog):
  declared config from ``-f``, or live usage/borrowed/wait percentiles
  from a dashboard ``--server``.
- ``kft serve -f <path>`` — materialise an InferenceService manifest:
  storage-initialize the model, resolve its runtime from the default
  registry, serve REST (+ optional gRPC) until SIGINT.
- ``kft gateway run -f <path>`` — run the L7 inference gateway from an
  ``InferenceGateway`` manifest: health-probed backend pools, edge canary
  split, activator buffering, per-tenant policy, /metrics; services with
  an ``autoscaling:`` section get a colocated KPA-style autoscaler that
  launches/drains ``replicaCommand`` subprocess replicas to follow load
  (scale-to-zero through the activator, prefix-KV transfer on remap).
- ``kft models``       — model registry verbs (list/show/register/promote/
  rollback/lineage) over the store at ``--root``/``KFT_REGISTRY_ROOT``.
- ``kft chaos run``    — run Job manifests under a declarative FaultPlan
  (``--plan plan.yaml``): inject every named failure at its trigger step,
  report what fired and whether the job recovered.
- ``kft lint``         — repo-native AST static analysis (``analysis/``):
  lock-discipline races, metric-name registry drift, JAX hot-loop sync
  violations, thread/clock hygiene, unseeded randomness; ``--strict`` is
  the CI gate (exit 0 clean / 1 findings / 2 usage error).
- ``kft doctor``       — device inventory as JAX reports it in this
  process: platform, ``device_kind``, count.
- ``kft trace dump``   — fetch tail-sampled request traces from a serving
  replica's ``/debug/traces``; ``--perfetto`` converts to Chrome/Perfetto
  ``trace_event`` JSON loadable in ``ui.perfetto.dev``.
- ``kft version``.

Everything here is a thin veneer over public APIs — the CLI owns argument
parsing and process lifecycle, nothing else.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time


def _load_docs(path: str) -> list[dict]:
    """A plain manifest file (possibly a multi-doc YAML stream), a
    kustomization file, or an overlay dir — `kubectl apply -f|-k` in one."""
    import yaml

    from kubeflow_tpu.platform import manifests

    if os.path.isdir(path):
        return manifests.build(path)
    with open(path) as f:
        docs = [d for d in yaml.safe_load_all(f) if d]
    if any(
        d.get("kind") == "Kustomization" or ("kind" not in d and "resources" in d)
        for d in docs
    ):
        return manifests.build(path)
    return docs


def _cmd_build(args) -> int:
    import yaml

    yaml.safe_dump_all(_load_docs(args.path), sys.stdout, sort_keys=False)
    return 0


def _cmd_run(args) -> int:
    import dataclasses

    from kubeflow_tpu.orchestrator.cluster import LocalCluster
    from kubeflow_tpu.orchestrator.envwire import WiringConfig
    from kubeflow_tpu.orchestrator.resources import Fleet
    from kubeflow_tpu.orchestrator.spec import JobConditionType, JobSpec
    from kubeflow_tpu.orchestrator.webhooks import AdmissionError
    from kubeflow_tpu.platform import manifests
    from kubeflow_tpu.platform.volumes import VolumeSpec
    from kubeflow_tpu.sched.queues import ClusterQueue, LocalQueue
    from kubeflow_tpu.tune.spec import ExperimentSpec

    prog = f"kft {args.cmd}"
    jobs: list[JobSpec] = []
    experiments: list[ExperimentSpec] = []
    queue_specs: list = []
    docs = _load_docs(args.file)
    if getattr(args, "queues", None):  # extra queue manifests ride along
        docs = list(docs) + _load_docs(args.queues)
    for doc in docs:
        try:
            parsed = manifests.parse(doc)
        except manifests.UnsupportedKind:
            # kubectl semantics: apply what we know, note what we skip
            print(
                f"{prog}: skipping unsupported kind "
                f"{doc.get('kind')!r}",
                file=sys.stderr,
            )
            continue
        except ValueError as e:  # supported kind, broken manifest: surface
            print(f"{prog}: invalid {doc.get('kind')} manifest: {e}",
                  file=sys.stderr)
            return 2
        if isinstance(parsed, JobSpec):
            jobs.append(parsed)
        elif isinstance(parsed, ExperimentSpec):
            experiments.append(parsed)
        elif isinstance(parsed, (ClusterQueue, LocalQueue)):
            queue_specs.append(parsed)
        elif isinstance(parsed, dict):  # ConfigMap — nothing to run
            continue
        elif isinstance(parsed, VolumeSpec):  # PVC — nothing to run
            continue
        else:
            print(
                f"{prog}: {doc.get('kind')!r} is not runnable here "
                "(use `kft serve` for InferenceService)",
                file=sys.stderr,
            )
            return 2
    if not jobs and not experiments:
        print(f"{prog}: no runnable manifests found", file=sys.stderr)
        return 2

    # --queue/--priority plumb straight into SchedulingPolicy
    if getattr(args, "queue", None) is not None or getattr(
        args, "priority", None
    ) is not None:
        for spec in jobs:
            sched = spec.run_policy.scheduling
            if args.queue is not None:
                sched = dataclasses.replace(sched, queue=args.queue)
            if args.priority is not None:
                sched = dataclasses.replace(sched, priority=args.priority)
            spec.run_policy = dataclasses.replace(
                spec.run_policy, scheduling=sched
            )

    fleet = Fleet.homogeneous(args.slices, args.topology)
    wiring = WiringConfig(
        platform=args.platform, devices_per_worker=args.devices_per_worker
    )
    failed = 0
    with LocalCluster(
        fleet=fleet, wiring=wiring, queues=queue_specs or None
    ) as cluster:
        uids = []
        for spec in jobs:
            try:
                uids.append((spec, cluster.submit(spec)))
            except AdmissionError as e:
                # e.g. an unknown LocalQueue — reject loudly at submit time
                print(f"{prog}: job/{spec.name} rejected: {e}",
                      file=sys.stderr)
                return 2
        deadline = time.monotonic() + args.timeout
        for spec, uid in uids:
            try:
                status = cluster.wait(
                    uid, timeout=max(0.01, deadline - time.monotonic())
                )
                phase = status.phase
            except TimeoutError:
                phase = "Timeout"
            ok = phase == JobConditionType.SUCCEEDED.value
            failed += 0 if ok else 1
            print(f"job/{spec.name}: {phase}")
            if args.logs or not ok:
                for rtype, rspec in spec.replicas.items():
                    for i in range(rspec.replicas):
                        try:
                            text = cluster.logs(uid, rtype, i)
                        except (KeyError, OSError):
                            continue
                        for line in text.splitlines():
                            print(f"  [{rtype}-{i}] {line}")
        for exp in experiments:
            from kubeflow_tpu.tune.controller import (
                ExperimentController,
                JobTrialRunner,
            )

            runner = JobTrialRunner(cluster, timeout_s=args.timeout)
            status = ExperimentController(exp, runner).run()
            best = status.optimal
            ok = best is not None
            failed += 0 if ok else 1
            print(
                f"experiment/{exp.name}: trials={len(status.trials)} "
                f"best={best.metrics.get('__objective__') if best else None} "
                f"assignment={dict(best.assignment.parameters) if best else {}}"
            )
    return 1 if failed else 0


def _cmd_serve(args) -> int:
    import asyncio

    from kubeflow_tpu.platform import manifests
    from kubeflow_tpu.serve import storage
    from kubeflow_tpu.serve.graph import GraphSpec
    from kubeflow_tpu.serve.runtimes import default_registry
    from kubeflow_tpu.serve.server import ModelServer
    from kubeflow_tpu.serve.spec import InferenceServiceSpec

    specs = []
    graphs: list[GraphSpec] = []
    for doc in _load_docs(args.file):
        try:
            parsed = manifests.parse(doc)
        except manifests.UnsupportedKind:
            print(
                f"kft serve: skipping unsupported kind {doc.get('kind')!r}",
                file=sys.stderr,
            )
            continue
        except ValueError as e:  # supported kind, broken manifest: surface
            print(f"kft serve: invalid {doc.get('kind')} manifest: {e}",
                  file=sys.stderr)
            return 2
        if isinstance(parsed, InferenceServiceSpec):
            specs.append(parsed)
        elif isinstance(parsed, GraphSpec):
            graphs.append(parsed)
    if not specs and not graphs:
        print("kft serve: no InferenceService/InferenceGraph manifests found",
              file=sys.stderr)
        return 2

    registry = default_registry()
    model_dir = args.model_dir or tempfile.mkdtemp(prefix="kft-models-")
    server = ModelServer(
        http_port=args.http_port,
        grpc_port=args.grpc_port,
        default_deadline_ms=args.default_deadline_ms,
        role=args.role,
    )
    for spec in specs:
        spec.validate()
        rt = registry.resolve(spec.predictor)
        local = (
            storage.download(spec.predictor.storage_uri, model_dir)
            if spec.predictor.storage_uri
            else None
        )
        # extra rides through to the runtime factory, matching the
        # controller's _materialise_component contract
        model = rt.factory(spec.name, local, **dict(spec.predictor.extra))
        server.register(model)
        print(f"inferenceservice/{spec.name}: loaded ({rt.name})")
    for g in graphs:  # after models: build validates every serviceName
        try:
            server.register_graph(g)
        except ValueError as e:
            print(f"kft serve: inferencegraph/{g.name}: {e}", file=sys.stderr)
            return 2
        print(f"inferencegraph/{g.name}: routing {sorted(g.services())}")

    async def main() -> None:
        await server.start_async()
        # the bound port (http_port=0 → ephemeral) — for scripts/tests
        sites = list(server._runner.sites) if server._runner else []
        port = (
            sites[0]._server.sockets[0].getsockname()[1]  # noqa: SLF001
            if sites
            else args.http_port
        )
        print(f"serving on http://127.0.0.1:{port}", flush=True)
        if args.port_file:
            with open(args.port_file, "w") as f:
                f.write(str(port))
        try:
            while True:
                await asyncio.sleep(3600)
        finally:
            await server.stop_async()

    try:
        asyncio.run(main())
    except KeyboardInterrupt:
        pass
    return 0


def _cmd_gateway(args) -> int:
    """Run the inference gateway from an ``InferenceGateway`` manifest —
    the front door two (or two hundred) ``kft serve`` processes sit
    behind. Prints the bound port (``--port-file`` for scripts), serves
    until SIGINT."""
    import asyncio

    from kubeflow_tpu.gateway.server import GatewayConfig, InferenceGateway

    docs = [d for d in _load_docs(args.file) if d]
    gw_docs = [d for d in docs if d.get("kind") == "InferenceGateway"]
    if len(gw_docs) != 1:
        print(
            f"kft gateway: expected exactly one InferenceGateway manifest "
            f"in {args.file}, found {len(gw_docs)}",
            file=sys.stderr,
        )
        return 2
    try:
        config = GatewayConfig.from_manifest(gw_docs[0])
    except (ValueError, KeyError, TypeError) as e:
        print(f"kft gateway: invalid manifest: {e}", file=sys.stderr)
        return 2
    gw = InferenceGateway(config, http_port=args.http_port)
    resume = "on" if config.stream_resume else "off"
    for r in gw.table.routes():
        urls = [b.url for b in gw.pool.backends_of(r.name)]
        print(
            f"service/{r.name}: canary={r.canary_percent}% "
            f"affinity={r.affinity} stream_resume={resume} backends={urls}"
        )

    async def main() -> None:
        await gw.start_async()
        # per-service autoscaling: a ServingAutoscaler + subprocess
        # ReplicaFleet per `autoscaling:` manifest section, colocated
        # with the gateway (the Knative autoscaler/activator layout) —
        # the activator's cold-episode kick ticks it out-of-band
        autoscaler = None
        fleets = []
        sources = []
        if config.autoscaling:
            from kubeflow_tpu.autoscale import (
                GatewaySignalSource,
                KPAConfig,
                ReplicaFleet,
                ServingAutoscaler,
                subprocess_launcher,
            )

            autoscaler = ServingAutoscaler(
                tick_interval_s=float(
                    next(iter(config.autoscaling.values())).get(
                        "tickIntervalS", 1.0
                    )
                )
            )
            for svc, auto in config.autoscaling.items():
                kpa = KPAConfig.from_manifest(auto)
                fleet = ReplicaFleet(
                    svc,
                    subprocess_launcher(list(auto["replicaCommand"])),
                    pool=gw.pool,
                    model=auto.get("model", svc),
                    role=auto.get("role", "both"),
                    transfer_prefix_kv=bool(
                        auto.get("transferPrefixKV", True)
                    ),
                )
                fleets.append(fleet)
                source = GatewaySignalSource(gw, svc)
                sources.append(source)
                autoscaler.add_service(svc, kpa, source, fleet)
                await fleet.scale_to(max(kpa.min_replicas, 0))
                print(
                    f"autoscaler/{svc}: target={kpa.target} replicas="
                    f"[{kpa.min_replicas},{kpa.max_replicas}] "
                    f"initial={fleet.current()}"
                )
            gw.activator.scale_up = autoscaler.kick
            autoscaler.start()
        print(f"gateway on http://127.0.0.1:{gw.http_port}", flush=True)
        if args.port_file:
            with open(args.port_file, "w") as f:
                f.write(str(gw.http_port))
        try:
            while True:
                await asyncio.sleep(3600)
        finally:
            if autoscaler is not None:
                await autoscaler.stop()
            for source in sources:
                await source.close()
            for fleet in fleets:
                await fleet.close()
            await gw.stop_async()

    try:
        asyncio.run(main())
    except KeyboardInterrupt:
        pass
    return 0


def _pipeline_ir(path: str, name: str | None = None):
    """A pipeline definition is either a .py file holding @pipeline objects
    (compiled here — the `kfp.compiler` analog) or an already-compiled IR
    JSON file (the portable wire format)."""
    from kubeflow_tpu.pipelines.compiler import compile_pipeline
    from kubeflow_tpu.pipelines.dsl import Pipeline
    from kubeflow_tpu.pipelines.ir import PipelineIR

    if path.endswith(".py"):
        import importlib.util

        spec = importlib.util.spec_from_file_location("_kft_pipeline", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        pipes = [v for v in vars(mod).values() if isinstance(v, Pipeline)]
        if name is not None:
            pipes = [p for p in pipes if p.name == name]
        if len(pipes) != 1:
            raise SystemExit(
                f"kft pipeline: {path} defines {len(pipes)} pipelines"
                + (f" named {name!r}" if name else "")
                + "; use --name to pick one"
            )
        return compile_pipeline(pipes[0])
    with open(path) as f:
        doc = json.load(f)
    return PipelineIR.from_dict(doc.get("spec", doc))


def _api(
    server: str,
    method: str,
    path: str,
    body: dict | None = None,
    *,
    prog: str = "kft pipeline",
) -> dict:
    import urllib.request

    req = urllib.request.Request(
        server.rstrip("/") + path,
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
        method=method,
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:  # noqa: S310
            return json.loads(resp.read())
    except Exception as e:
        import urllib.error

        if isinstance(e, urllib.error.HTTPError):
            raise SystemExit(
                f"{prog}: {method} {path} → HTTP {e.code}: "
                f"{e.read().decode(errors='replace')[:500]}"
            ) from e
        raise SystemExit(f"{prog}: cannot reach {server}: {e}") from e


def _parse_params(pairs: list[str]) -> dict:
    out = {}
    for pair in pairs or []:
        if "=" not in pair:
            raise SystemExit(f"kft pipeline: -p expects key=value, got {pair!r}")
        k, _, v = pair.partition("=")
        try:
            out[k] = json.loads(v)   # numbers/bools/json pass through typed
        except json.JSONDecodeError:
            out[k] = v
    return out


def _cmd_pipeline(args) -> int:
    if args.action in ("compile", "upload") and not args.file:
        raise SystemExit(f"kft pipeline {args.action}: -f is required")
    if args.action == "run" and not args.server and not args.file:
        raise SystemExit("kft pipeline run: -f is required without --server")
    if args.action in ("upload", "list") and not args.server:
        raise SystemExit(f"kft pipeline {args.action}: --server is required")
    if args.action == "compile":
        ir = _pipeline_ir(args.file, args.name)
        text = json.dumps(ir.to_dict(), indent=1, sort_keys=True)
        if args.output:
            with open(args.output, "w") as f:
                f.write(text)
        else:
            print(text)
        return 0

    if args.action == "upload":
        ir = _pipeline_ir(args.file, args.name)
        out = _api(args.server, "POST", "/apis/v2beta1/pipelines",
                   {"spec": ir.to_dict()})
        print(f"pipeline/{out['name']}: uploaded ({out['tasks']} tasks)")
        return 0

    if args.action == "list":
        out = _api(args.server, "GET", "/apis/v2beta1/pipelines")
        for p in out["pipelines"]:
            print(f"{p['name']}\ttasks={p['tasks']}\t{p['description']}")
        runs = _api(args.server, "GET", "/apis/v2beta1/runs")["runs"]
        for r in runs:
            print(f"run/{r['run_id']}\t{r['pipeline']}\t{r['state']}")
        return 0

    # run
    params = _parse_params(args.param)
    if args.server:
        if args.file:
            body = {"spec": _pipeline_ir(args.file, args.name).to_dict()}
        else:
            if not args.name:
                raise SystemExit("kft pipeline run: need -f or --name")
            body = {"pipeline": args.name}
        body["parameters"] = params
        rid = _api(args.server, "POST", "/apis/v2beta1/runs", body)["run_id"]
        deadline = time.monotonic() + args.timeout
        while True:
            rec = _api(args.server, "GET", f"/apis/v2beta1/runs/{rid}")
            if rec["state"] not in ("PENDING", "RUNNING"):
                break
            if time.monotonic() > deadline:
                print(f"run/{rid}: still {rec['state']} after "
                      f"{args.timeout}s", file=sys.stderr)
                return 1
            time.sleep(0.2)
    else:
        from kubeflow_tpu.pipelines.artifacts import ArtifactStore
        from kubeflow_tpu.pipelines.cache import StepCache
        from kubeflow_tpu.pipelines.runner import PipelineRunner

        ir = _pipeline_ir(args.file, args.name)
        root = args.artifacts or tempfile.mkdtemp(prefix="kft-pipeline-")
        runner = PipelineRunner(
            artifact_store=ArtifactStore(os.path.join(root, "artifacts")),
            cache=StepCache(os.path.join(root, "cache")),
        )
        res = runner.run(ir, params)
        rec = {
            "run_id": res.run_id, "state": res.state,
            "tasks": {
                n: {"state": t.state, "cache_hit": t.cache_hit,
                    "error": t.error}
                for n, t in res.tasks.items()
            },
        }
    for name, t in rec["tasks"].items():
        mark = " (cached)" if t.get("cache_hit") else ""
        err = f" — {t['error']}" if t.get("error") else ""
        print(f"  task/{name}: {t['state']}{mark}{err}")
    if rec.get("error"):  # run-level failure (outside any task)
        print(f"run error: {rec['error']}", file=sys.stderr)
    print(f"run/{rec['run_id']}: {rec['state']}")
    return 0 if rec["state"] == "SUCCEEDED" else 1


def _cmd_models(args) -> int:
    """Model-registry verbs (the model-registry CLI/BFF analog): operate
    in-process on the store under ``--root`` / ``KFT_REGISTRY_ROOT``."""
    from kubeflow_tpu.registry import stages as reg_stages
    from kubeflow_tpu.registry.store import ModelStore

    root = args.root or os.environ.get("KFT_REGISTRY_ROOT")
    if not root:
        raise SystemExit(
            "kft models: need --root or KFT_REGISTRY_ROOT (registry dir)"
        )
    store = ModelStore(root)

    def need(what, value):
        if value is None:
            raise SystemExit(f"kft models {args.action}: {what} is required")
        return value

    try:
        if args.action == "list":
            for m in store.list_models():
                stages = " ".join(
                    f"{s}=v{v}" for s, v in sorted(m.stages.items())
                ) or "-"
                print(f"{m.name}\tversions={m.latest_version}\t{stages}")
            return 0
        if args.action == "show":
            name = need("NAME", args.name)
            for v in store.list_versions(name):
                print(
                    f"v{v.version}\t{v.stage}\t{v.sha256[:12]}\t"
                    f"{json.dumps(v.metadata, sort_keys=True)}"
                )
            return 0
        if args.action == "register":
            name = need("NAME", args.name)
            path = need("--path", args.path)
            mv = store.register_version(
                name, path, stage=args.stage,
                metadata=_parse_params(args.param),
            )
            print(f"{mv.ref}: sha256={mv.sha256[:12]} stage={mv.stage}")
            return 0
        if args.action == "promote":
            name = need("NAME", args.name)
            version = need("--version", args.version)
            out = reg_stages.promote(
                store, name, int(version), args.stage or "production"
            )
            print(
                f"{name}@{out['stage']}: v{out['version']}"
                + (f" (was v{out['previous']})" if out["previous"] else "")
            )
            return 0
        if args.action == "rollback":
            name = need("NAME", args.name)
            out = reg_stages.rollback(store, name, args.stage or "production")
            print(
                f"{name}@{out['stage']}: "
                + (f"v{out['version']}" if out["version"] else "(empty)")
                + f" (rolled back v{out['previous']})"
            )
            return 0
        # lineage
        name = need("NAME", args.name)
        versions = (
            [store.get_version(name, int(args.version))]
            if args.version else store.list_versions(name)
        )
        for v in versions:
            for e in store.lineage_of(name, v.version):
                print(
                    f"v{v.version}\t{e.kind}\t{e.ref}\t"
                    f"{json.dumps(e.metadata, sort_keys=True)}"
                )
        return 0
    except (KeyError, ValueError, FileNotFoundError, RuntimeError) as e:
        print(f"kft models {args.action}: {e}", file=sys.stderr)
        return 1
    finally:
        store.close()


def _cmd_queues(args) -> int:
    """Queue verbs (the ``kueuectl list/describe`` analog): render the
    declared ClusterQueue/LocalQueue config from ``-f`` manifests, or the
    live quota/usage/wait view from a dashboard server (``--server``)."""
    from kubeflow_tpu.platform import manifests
    from kubeflow_tpu.sched.queues import (
        ClusterQueue, LocalQueue, QueueConfig,
    )

    if args.server:
        rows = _api(args.server, "GET", "/api/queues", prog="kft queues")
    else:
        if not args.file:
            raise SystemExit(
                "kft queues: need -f QUEUES_YAML (ClusterQueue/LocalQueue "
                "manifests) or --server DASHBOARD_URL"
            )
        specs = []
        for doc in _load_docs(args.file):
            try:
                parsed = manifests.parse(doc)
            except (manifests.UnsupportedKind, ValueError):
                continue
            if isinstance(parsed, (ClusterQueue, LocalQueue)):
                specs.append(parsed)
        try:
            config = QueueConfig.from_specs(specs)
        except ValueError as e:
            print(f"kft queues: invalid queue config: {e}", file=sys.stderr)
            return 2
        rows = [
            {
                "name": cq.name,
                "cohort": cq.cohort,
                "nominal": dict(cq.quota),
                "usage": {},
                "borrowed": {},
                "borrowing_limit": cq.borrowing_limit,
                "preemption": cq.preemption.to_dict(),
                "local_queues": config.local_queues_of(cq.name),
                "admitted": None,
                "pending": None,
                "wait_p50_s": None,
                "wait_p95_s": None,
            }
            for cq in config.cluster_queues.values()
        ]

    def fmt_chips(d):
        return ",".join(f"{g}:{c}" for g, c in sorted(d.items())) or "-"

    if args.action == "list":
        for r in rows:
            print(
                f"{r['name']}\tcohort={r['cohort'] or '-'}\t"
                f"nominal={fmt_chips(r['nominal'])}\t"
                f"used={fmt_chips(r['usage'])}\t"
                f"borrowed={fmt_chips(r['borrowed'])}\t"
                f"pending={r['pending'] if r['pending'] is not None else '-'}\t"
                f"localqueues={','.join(r['local_queues']) or '-'}"
            )
        return 0

    # show NAME
    if not args.name:
        raise SystemExit("kft queues show: NAME is required")
    row = next((r for r in rows if r["name"] == args.name), None)
    if row is None:
        print(
            f"kft queues show: unknown ClusterQueue {args.name!r} "
            f"(known: {sorted(r['name'] for r in rows)})",
            file=sys.stderr,
        )
        return 1
    p50, p95 = row["wait_p50_s"], row["wait_p95_s"]
    print(f"name:            {row['name']}")
    print(f"cohort:          {row['cohort'] or '-'}")
    print(f"nominal chips:   {fmt_chips(row['nominal'])}")
    print(f"used chips:      {fmt_chips(row['usage'])}")
    print(f"borrowed chips:  {fmt_chips(row['borrowed'])}")
    print(f"borrowing limit: {row['borrowing_limit'] if row['borrowing_limit'] is not None else 'unbounded'}")
    print(f"preemption:      {json.dumps(row['preemption'], sort_keys=True)}")
    print(f"local queues:    {', '.join(row['local_queues']) or '-'}")
    print(f"admitted:        {row['admitted'] if row['admitted'] is not None else '-'}")
    print(f"pending:         {row['pending'] if row['pending'] is not None else '-'}")
    print(
        "queue wait:      "
        + (
            f"p50={p50:.3f}s p95={p95:.3f}s"
            if p50 is not None
            else "no admissions observed"
        )
    )
    return 0


def _cmd_chaos(args) -> int:
    """Run Job manifests under a FaultPlan: the CLI spelling of the chaos
    harness — inject every declared failure at its trigger step and report
    whether the platform recovered (exit 0 iff every job Succeeded and
    every fault fired)."""
    import yaml

    from kubeflow_tpu.chaos import ChaosRunner, FaultPlan
    from kubeflow_tpu.orchestrator.cluster import LocalCluster
    from kubeflow_tpu.orchestrator.envwire import WiringConfig
    from kubeflow_tpu.orchestrator.resources import Fleet
    from kubeflow_tpu.orchestrator.spec import JobSpec
    from kubeflow_tpu.platform import manifests

    with open(args.plan) as f:
        plan = FaultPlan.from_dict(yaml.safe_load(f) or {})
    jobs: list[JobSpec] = []
    for doc in _load_docs(args.file):
        try:
            parsed = manifests.parse(doc)
        except manifests.UnsupportedKind:
            print(
                f"kft chaos: skipping unsupported kind {doc.get('kind')!r}",
                file=sys.stderr,
            )
            continue
        except ValueError as e:
            print(f"kft chaos: invalid {doc.get('kind')} manifest: {e}",
                  file=sys.stderr)
            return 2
        if isinstance(parsed, JobSpec):
            jobs.append(parsed)
    if not jobs:
        print("kft chaos: no Job manifests found", file=sys.stderr)
        return 2

    fleet = Fleet.homogeneous(args.slices, args.topology)
    wiring = WiringConfig(
        platform=args.platform, devices_per_worker=args.devices_per_worker
    )
    failed = 0
    with LocalCluster(
        fleet=fleet, wiring=wiring, restart_backoff_base=0.1,
        resync_period=0.05,
    ) as cluster:
        for spec in jobs:
            uid = cluster.submit(spec)
            report = ChaosRunner(cluster, uid, plan).drive(
                timeout=args.timeout
            )
            ok = report["phase"] == "Succeeded" and not report["pending"]
            failed += 0 if ok else 1
            print(f"job/{spec.name}: {report['phase']} "
                  f"restarts={report['restart_count']}")
            for rec in report["fired"]:
                rc = rec["recovered_after_s"]
                print(
                    f"  fired {rec['fault']['kind']} at step "
                    f"{rec['at_observed_step']} on {rec['targets']}"
                    + (f" — recovered in {rc:.2f}s" if rc is not None else "")
                )
            for fd in report["pending"]:
                print(f"  NEVER FIRED: {fd['kind']} (at_step={fd['at_step']})")
            if args.json:
                print(json.dumps(report))
    return 1 if failed else 0


def _cmd_lint(args) -> int:
    """Run the repo-native static-analysis passes (``analysis/``): exit 0
    clean, 1 on findings, 2 on usage errors. ``--strict`` also fails on
    warnings and stale baseline entries — the CI spelling."""
    from kubeflow_tpu.analysis import engine as lint_engine

    root = args.root or os.getcwd()
    config = lint_engine.load_config(root)
    if args.baseline is not None:
        config.baseline = args.baseline
    try:
        result = lint_engine.run_lint(
            config,
            rules=args.rule or None,
            paths=args.paths or None,
            baseline=not (args.no_baseline or args.update_baseline),
        )
    except ValueError as e:  # unknown rule
        print(f"kft lint: {e}", file=sys.stderr)
        return 2
    if result.parse_errors:
        for err in result.parse_errors:
            print(f"kft lint: cannot parse {err}", file=sys.stderr)
        return 2

    if args.update_baseline:
        if not config.baseline:
            print("kft lint: no baseline path configured", file=sys.stderr)
            return 2
        path = os.path.join(root, config.baseline)
        lint_engine.write_baseline(result.findings, path)
        print(
            f"kft lint: pinned {len(result.findings)} finding(s) to "
            f"{config.baseline}"
        )
        return 0

    if args.json:
        print(json.dumps(result.to_dict(), indent=1, sort_keys=True))
    else:
        for f in result.findings:
            print(f.render())
        tail = (
            f"kft lint: {len(result.findings)} finding(s) in "
            f"{result.files} files"
        )
        if result.baseline_matched:
            tail += f" ({result.baseline_matched} pinned by baseline)"
        if result.noqa_suppressed:
            tail += f" ({result.noqa_suppressed} noqa-suppressed)"
        print(tail)
        for fp in result.stale_baseline:
            print(
                f"kft lint: stale baseline entry {list(fp)} — prune it",
                file=sys.stderr,
            )

    failing = [
        f
        for f in result.findings
        if args.strict or f.severity == "error"
    ]
    if args.strict and result.stale_baseline:
        return 1
    return 1 if failing else 0


def _cmd_doctor(args) -> int:
    import jax

    devices = jax.devices()  # raises if the configured platform is absent
    print(json.dumps({
        "backend": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "devices": len(devices),
    }))
    return 0


def _cmd_trace(args) -> int:
    data = _api(
        args.server, "GET", f"/debug/traces?limit={args.limit}",
        prog="kft trace",
    )
    if args.perfetto:
        from kubeflow_tpu.obs.trace import to_perfetto

        data = to_perfetto(data)
    text = json.dumps(data, indent=1, sort_keys=True)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text + "\n")
        n = len(data.get("traceEvents", []) if args.perfetto
                else data.get("traces", []))
        print(f"wrote {args.output} ({n} "
              f"{'events' if args.perfetto else 'traces'})")
    else:
        print(text)
    return 0


def _loadgen_mix(args):
    """Build the WorkloadMix from ``--tenant`` key=value specs (repeatable);
    no ``--tenant`` → one default tenant carrying --slo-ms/--deadline-ms."""
    from kubeflow_tpu.loadgen import TenantSpec, WorkloadMix

    tenants = []
    for spec in args.tenant or ():
        kv = dict(part.split("=", 1) for part in spec.split(",") if part)
        try:
            tenants.append(TenantSpec(
                name=kv.pop("name"),
                weight=float(kv.pop("weight", 1.0)),
                priority=(
                    int(kv.pop("priority")) if "priority" in kv else None
                ),
                deadline_ms=(
                    float(kv.pop("deadline_ms"))
                    if "deadline_ms" in kv else None
                ),
                slo_ms=float(kv.pop("slo_ms")) if "slo_ms" in kv else None,
                adapter=kv.pop("adapter", None),
            ))
        except KeyError as e:
            raise SystemExit(f"kft loadgen: --tenant spec missing {e}")
        if kv:
            raise SystemExit(
                f"kft loadgen: unknown --tenant key(s) {sorted(kv)}"
            )
    if not tenants:
        tenants = [TenantSpec(
            "default",
            deadline_ms=args.deadline_ms,
            slo_ms=args.slo_ms,
        )]
    return WorkloadMix(
        prompt_lens=tuple(int(x) for x in args.prompt_lens.split(",")),
        output_lens=tuple(int(x) for x in args.output_lens.split(",")),
        tenants=tuple(tenants),
        seed=args.seed,
    )


def _loadgen_arrivals(args):
    """Arrival source from flags: a seeded process or a replayed dump."""
    from kubeflow_tpu.loadgen import (
        OnOffArrivals,
        PoissonArrivals,
        ReplayArrivals,
    )

    if args.process == "replay":
        if not args.trace_file:
            raise SystemExit(
                "kft loadgen: --process replay needs --trace-file "
                "(a `kft trace dump` output)"
            )
        return ReplayArrivals.from_file(args.trace_file)
    if args.process == "onoff":
        return OnOffArrivals(
            base_rps=args.rate, burst_rps=args.burst_rps,
            period_s=args.period_s, duration_s=args.duration,
            seed=args.seed,
        )
    return PoissonArrivals(
        rate_rps=args.rate, duration_s=args.duration, seed=args.seed
    )


def _cmd_loadgen_schedule(args) -> int:
    """Print the seeded arrival schedule — the determinism contract made
    inspectable: the same flags always print the same offsets."""
    arrivals = _loadgen_arrivals(args)
    schedule = arrivals.schedule()
    out = {
        "process": args.process,
        "seed": args.seed,
        "n": len(schedule),
        "offsets_s": [round(t, 6) for t in schedule],
    }
    print(json.dumps(out, indent=1))
    return 0


def _cmd_loadgen_run(args) -> int:
    """Open-loop load against an ALREADY-RUNNING gateway (external
    process): fire the schedule, scrape /metrics before and after, emit
    the goodput report. The in-process bench/smoke path is
    ``python bench.py serving_load``."""
    import asyncio

    from kubeflow_tpu.loadgen import LoadClient, build_report, scrape_metrics

    arrivals = _loadgen_arrivals(args)
    schedule = arrivals.schedule()
    mix = _loadgen_mix(args)
    if args.process == "replay":
        specs = mix.plan_for_replay(
            arrivals.requests, cap_new_tokens=args.max_new_tokens
        )
    else:
        specs = mix.plan(len(schedule))
    client = LoadClient(
        args.url, args.model,
        stream=not args.no_stream,
        request_timeout_s=args.timeout,
    )

    async def drive():
        metrics_url = args.url.rstrip("/") + "/metrics"
        try:
            baseline = await scrape_metrics(metrics_url)
        except Exception:
            baseline = None  # gateway may not expose /metrics — degrade
        results = await client.run(schedule, specs)
        try:
            after = await scrape_metrics(metrics_url)
        except Exception:
            after = None
        traces = None
        if args.traces_url:
            traces = json.loads(await scrape_metrics(
                args.traces_url.rstrip("/") + "/debug/traces?limit=256"
            ))
        return build_report(
            results=results,
            run={
                "bench": "loadgen_run",
                "url": args.url,
                "model": args.model,
                "process": args.process,
                "seed": args.seed,
                "offered_requests": len(schedule),
                "duration_s": args.duration,
            },
            gateway_metrics=after,
            baseline_metrics=baseline,
            traces=traces,
        )

    report = asyncio.run(drive())
    text = json.dumps(report, indent=1, sort_keys=True)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text + "\n")
        overall = report["goodput"]["overall"]
        print(
            f"wrote {args.output} (offered={overall['offered']} "
            f"goodput={overall['goodput']})"
        )
    else:
        print(text)
    overall = report["goodput"]["overall"]
    return 1 if overall["error"] else 0


def _cmd_version(_args) -> int:
    import kubeflow_tpu

    print(getattr(kubeflow_tpu, "__version__", "0.dev"))
    return 0


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="kft", description=__doc__.split("\n")[0])
    sub = p.add_subparsers(dest="cmd", required=True)

    b = sub.add_parser("build", help="resolve a kustomize overlay to YAML")
    b.add_argument("path")
    b.set_defaults(fn=_cmd_build)

    def add_run_flags(parser) -> None:
        parser.add_argument("-f", "--file", required=True,
                            help="manifest file or overlay dir")
        parser.add_argument("--timeout", type=float, default=300.0)
        parser.add_argument("--logs", action="store_true",
                            help="print worker logs even on success")
        parser.add_argument("--slices", type=int, default=1)
        parser.add_argument("--topology", default="2x2")
        parser.add_argument("--platform", default="cpu_sim",
                            choices=("cpu_sim", "tpu"))
        parser.add_argument("--devices-per-worker", type=int, default=1)
        parser.add_argument("--queue", default=None,
                            help="submit every job to this LocalQueue "
                                 "(overrides schedulingPolicy.queue)")
        parser.add_argument("--priority", type=int, default=None,
                            help="scheduling priority for every job "
                                 "(overrides schedulingPolicy.priorityValue)")
        parser.add_argument("--queues", default=None,
                            help="ClusterQueue/LocalQueue manifest file — "
                                 "enables quota scheduling (queue manifests "
                                 "inside -f work too)")

    r = sub.add_parser("run", help="run Job/Experiment manifests to completion")
    add_run_flags(r)
    r.set_defaults(fn=_cmd_run)

    jb = sub.add_parser(
        "jobs", help="job verbs: submit manifests with scheduling overrides"
    )
    jb.add_argument("action", choices=("submit",))
    add_run_flags(jb)
    jb.set_defaults(fn=_cmd_run)

    q = sub.add_parser(
        "queues", help="quota queues: list/show ClusterQueues"
    )
    q.add_argument("action", choices=("list", "show"))
    q.add_argument("name", nargs="?", default=None,
                   help="show: ClusterQueue name")
    q.add_argument("-f", "--file", default=None,
                   help="ClusterQueue/LocalQueue manifest file or overlay")
    q.add_argument("--server", default=None,
                   help="dashboard base URL for the live quota/usage/wait "
                        "view (default: static view of -f)")
    q.set_defaults(fn=_cmd_queues)

    s = sub.add_parser("serve", help="serve InferenceService manifests")
    s.add_argument("-f", "--file", required=True)
    s.add_argument("--http-port", type=int, default=8080)
    s.add_argument("--grpc-port", type=int, default=None)
    s.add_argument("--model-dir", default=None,
                   help="storage-initializer destination (default: tmpdir)")
    s.add_argument("--port-file", default=None,
                   help="write the bound HTTP port here once listening")
    s.add_argument("--role", choices=("both", "prefill", "decode"),
                   default="both",
                   help="disaggregated-serving role: 'prefill' replicas "
                        "only answer kv_span:prefill pulls, 'decode' "
                        "replicas pull their prefill KV from the peer the "
                        "gateway stamps (x-kft-prefill-peer)")
    s.add_argument("--default-deadline-ms", type=float, default=None,
                   help="end-to-end budget applied to requests arriving "
                        "without an x-kft-deadline-ms header (KServe "
                        "request-timeout analog; default: unlimited)")
    s.set_defaults(fn=_cmd_serve)

    gw = sub.add_parser(
        "gateway", help="run the L7 inference gateway (Istio/Knative analog)"
    )
    gw.add_argument("action", choices=("run",))
    gw.add_argument("-f", "--file", required=True,
                    help="InferenceGateway manifest file")
    gw.add_argument("--http-port", type=int, default=8081)
    gw.add_argument("--port-file", default=None,
                    help="write the bound HTTP port here once listening")
    gw.set_defaults(fn=_cmd_gateway)

    pl = sub.add_parser(
        "pipeline", help="compile/upload/run pipelines (KFP-CLI analog)"
    )
    pl.add_argument("action",
                    choices=("compile", "upload", "run", "list"))
    pl.add_argument("-f", "--file", default=None,
                    help="@pipeline .py file or compiled IR .json")
    pl.add_argument("--name", default=None,
                    help="pipeline name (pick from .py / server registry)")
    pl.add_argument("-o", "--output", default=None,
                    help="compile: write IR JSON here instead of stdout")
    pl.add_argument("-p", "--param", action="append", default=[],
                    help="run: pipeline parameter key=value (repeatable)")
    pl.add_argument("--server", default=None,
                    help="pipelines API base URL (default: run in-process)")
    pl.add_argument("--artifacts", default=None,
                    help="local run: artifact/cache root (default: tmpdir)")
    pl.add_argument("--timeout", type=float, default=300.0)
    pl.set_defaults(fn=_cmd_pipeline)

    mo = sub.add_parser(
        "models", help="model registry: list/register/promote/lineage"
    )
    mo.add_argument(
        "action",
        choices=("list", "show", "register", "promote", "rollback",
                 "lineage"),
    )
    mo.add_argument("name", nargs="?", default=None,
                    help="registered model name")
    mo.add_argument("--root", default=None,
                    help="registry root dir (default: $KFT_REGISTRY_ROOT)")
    mo.add_argument("--path", default=None,
                    help="register: model payload file/dir to ingest")
    mo.add_argument("--version", default=None,
                    help="promote/lineage: version number")
    mo.add_argument("--stage", default=None,
                    help="register/promote/rollback: stage "
                         "(default: production for promote/rollback)")
    mo.add_argument("-p", "--param", action="append", default=[],
                    help="register: metadata key=value (repeatable)")
    mo.set_defaults(fn=_cmd_models)

    ch = sub.add_parser(
        "chaos", help="run Job manifests under a fault-injection plan"
    )
    ch.add_argument("action", choices=("run",))
    ch.add_argument("-f", "--file", required=True,
                    help="Job manifest file or overlay dir")
    ch.add_argument("--plan", required=True,
                    help="FaultPlan YAML/JSON ({seed, faults: [{kind, ...}]})")
    ch.add_argument("--timeout", type=float, default=300.0)
    ch.add_argument("--slices", type=int, default=1)
    ch.add_argument("--topology", default="2x2")
    ch.add_argument("--platform", default="cpu_sim",
                    choices=("cpu_sim", "tpu"))
    ch.add_argument("--devices-per-worker", type=int, default=1)
    ch.add_argument("--json", action="store_true",
                    help="also print the machine-readable chaos report")
    ch.set_defaults(fn=_cmd_chaos)

    li = sub.add_parser(
        "lint", help="repo-native AST invariant checks (analysis/ passes)"
    )
    li.add_argument("paths", nargs="*", default=[],
                    help="files/dirs to lint (default: [tool.kft-lint] "
                         "include globs)")
    li.add_argument("--strict", action="store_true",
                    help="fail on warnings and stale baseline entries too")
    li.add_argument("--rule", action="append", default=[],
                    help="run only this rule (repeatable)")
    li.add_argument("--json", action="store_true",
                    help="machine-readable findings document")
    li.add_argument("--root", default=None,
                    help="repo root holding pyproject.toml (default: cwd)")
    li.add_argument("--baseline", default=None,
                    help="override the baseline file path")
    li.add_argument("--no-baseline", action="store_true",
                    help="report pinned legacy findings too")
    li.add_argument("--update-baseline", action="store_true",
                    help="pin the current findings as the new baseline")
    li.set_defaults(fn=_cmd_lint)

    d = sub.add_parser("doctor", help="device inventory (platform, kind, count)")
    d.set_defaults(fn=_cmd_doctor)

    tr = sub.add_parser(
        "trace", help="request-tracing verbs against a serving replica"
    )
    tr_sub = tr.add_subparsers(dest="action", required=True)
    trd = tr_sub.add_parser(
        "dump",
        help="fetch tail-sampled traces from /debug/traces "
             "(--perfetto → Chrome/Perfetto trace_event JSON)",
    )
    trd.add_argument("--server", required=True,
                     help="replica base URL, e.g. http://127.0.0.1:8000")
    trd.add_argument("--limit", type=int, default=64,
                     help="max traces to fetch (newest first)")
    trd.add_argument("--perfetto", action="store_true",
                     help="emit Perfetto trace_event JSON instead of the "
                          "raw snapshot")
    trd.add_argument("-o", "--output", default=None,
                     help="write to a file instead of stdout")
    trd.set_defaults(fn=_cmd_trace)

    lg = sub.add_parser(
        "loadgen",
        help="open-loop load generation: seeded traffic against a live "
             "gateway, SLO-goodput reports",
    )
    lg_sub = lg.add_subparsers(dest="action", required=True)

    def add_loadgen_flags(parser) -> None:
        parser.add_argument("--process", default="poisson",
                            choices=("poisson", "onoff", "replay"),
                            help="arrival process (replay needs "
                                 "--trace-file)")
        parser.add_argument("--rate", type=float, default=4.0,
                            help="arrival rate rps (onoff: base rate)")
        parser.add_argument("--burst-rps", type=float, default=16.0,
                            help="onoff: on-phase rate")
        parser.add_argument("--period-s", dest="period_s", type=float,
                            default=4.0, help="onoff: on+off cycle length")
        parser.add_argument("--duration", type=float, default=10.0,
                            help="schedule length in seconds")
        parser.add_argument("--seed", type=int, default=0,
                            help="same seed -> identical schedule + draws")
        parser.add_argument("--trace-file", default=None,
                            help="`kft trace dump` output to replay")

    lgs = lg_sub.add_parser(
        "schedule",
        help="print the seeded arrival offsets (determinism check: same "
             "flags, same offsets, every time)",
    )
    add_loadgen_flags(lgs)
    lgs.set_defaults(fn=_cmd_loadgen_schedule)

    lgr = lg_sub.add_parser(
        "run",
        help="drive an already-running gateway over HTTP/SSE and emit "
             "the goodput report",
    )
    add_loadgen_flags(lgr)
    lgr.add_argument("--url", required=True,
                     help="gateway base URL, e.g. http://127.0.0.1:8080")
    lgr.add_argument("--model", default="m",
                     help="served model name for /v2/models/{m} paths")
    lgr.add_argument("--prompt-lens", default="8,16,32",
                     help="comma list of prompt lengths to mix")
    lgr.add_argument("--output-lens", default="4,8,16",
                     help="comma list of output budgets to mix")
    lgr.add_argument("--max-new-tokens", type=int, default=None,
                     help="replay: cap each request's output budget")
    lgr.add_argument("--tenant", action="append", default=None,
                     help="repeatable tenant spec: name=interactive,"
                          "weight=2,priority=2,deadline_ms=30000,"
                          "slo_ms=2000,adapter=a1")
    lgr.add_argument("--slo-ms", type=float, default=None,
                     help="single-tenant shorthand: accounting SLO")
    lgr.add_argument("--deadline-ms", type=float, default=None,
                     help="single-tenant shorthand: wire deadline header")
    lgr.add_argument("--no-stream", action="store_true",
                     help="use unary /generate instead of SSE streaming")
    lgr.add_argument("--timeout", type=float, default=180.0,
                     help="per-request client timeout")
    lgr.add_argument("--traces-url", default=None,
                     help="replica base URL to scrape /debug/traces from")
    lgr.add_argument("-o", "--output", default=None,
                     help="write the report JSON to a file")
    lgr.set_defaults(fn=_cmd_loadgen_run)

    v = sub.add_parser("version")
    v.set_defaults(fn=_cmd_version)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
