#!/usr/bin/env bash
# Fast pre-tier-1 gate: syntax + import breakage fails in seconds, not
# after minutes of pytest collection. Run from the repo root:
#
#   bash scripts/smoke.sh
#
# 1. `compileall` over the package — any SyntaxError fails the sweep.
# 2. Import every `kubeflow_tpu` module on the CPU backend — a broken
#    top-level import (missing dep, bad re-export, circular import)
#    fails with the offending module named.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

echo "== compileall =="
python -m compileall -q kubeflow_tpu tests scripts bench.py

echo "== import sweep =="
python - <<'EOF'
import importlib
import pkgutil
import sys

import kubeflow_tpu

failures = []
mods = sorted(
    m.name
    for m in pkgutil.walk_packages(kubeflow_tpu.__path__, "kubeflow_tpu.")
    # __main__ executes the CLI at import; everything else must be inert
    if not m.name.endswith("__main__")
)
for name in mods:
    try:
        importlib.import_module(name)
    except Exception as e:  # noqa: BLE001 — report every breakage at once
        failures.append((name, f"{type(e).__name__}: {e}"))
print(f"imported {len(mods) - len(failures)}/{len(mods)} modules")
for name, err in failures:
    print(f"FAIL {name}: {err}", file=sys.stderr)
sys.exit(1 if failures else 0)
EOF

echo "== kft lint --strict (repo-native invariant checks) =="
# AST passes over the whole package: lock discipline, metric-name registry,
# JAX hot-loop sync rules, thread/clock hygiene, seedable randomness.
# Anything beyond the pinned lint_baseline.json fails the gate.
python -m kubeflow_tpu lint --strict

echo "== 20-step overlapped Trainer.fit (prefetch on, accum=2) =="
python - <<'EOF'
import os, sys, threading

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=2"
    ).strip()
import jax  # noqa: E402
import optax  # noqa: E402

from kubeflow_tpu.core.mesh import MeshSpec  # noqa: E402
from kubeflow_tpu.data.synthetic import (  # noqa: E402
    ClassPrototypeDataset, local_shard_iterator,
)
from kubeflow_tpu.models.mnist_cnn import (  # noqa: E402
    MnistCNN, make_init_fn, make_loss_fn,
)
from kubeflow_tpu.train.loop import TrainConfig, Trainer  # noqa: E402
from kubeflow_tpu.train.prefetch import live_kft_threads  # noqa: E402

model = MnistCNN()
trainer = Trainer(
    init_params=make_init_fn(model),
    loss_fn=make_loss_fn(model),
    optimizer=optax.adam(1e-3),
    config=TrainConfig(
        mesh=MeshSpec.data_parallel(jax.device_count()),
        global_batch=16,
        steps=20,
        log_every=10,
        check_numerics="off",
        prefetch_depth=2,
        grad_accum_steps=2,
    ),
)
_, history = trainer.fit(local_shard_iterator(ClassPrototypeDataset(), 16))
assert history and history[-1]["step"] == 20, history
assert history[-1]["steps_per_sec"] > 0, history[-1]
assert "compile_ms" in history[0], history[0]
# clean shutdown: the prefetch producer and metric drain must be joined,
# and nothing non-daemon may be left to wedge interpreter exit
leaked = live_kft_threads()
assert not leaked, f"leaked overlap threads: {leaked}"
non_daemon = [
    t.name for t in threading.enumerate()
    if t is not threading.main_thread() and not t.daemon
]
assert not non_daemon, f"leaked non-daemon threads: {non_daemon}"
print(f"fit OK: steps_per_sec={history[-1]['steps_per_sec']:.3g} "
      f"compile_ms={history[0]['compile_ms']:.1f}")
EOF

echo "== pipelined decode: 2 concurrent requests, carry uploads << chunks =="
python - <<'EOF'
import threading

import jax
import jax.numpy as jnp
import numpy as np  # noqa: E402

from kubeflow_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, TransformerLM,
)
from kubeflow_tpu.serve.engine import LMEngine  # noqa: E402

cfg = TransformerConfig(
    vocab_size=64, d_model=16, n_layers=1, n_heads=2, d_ff=32, causal=True,
    max_seq_len=128, attn_impl="reference", dtype=jnp.float32,
)
model = TransformerLM(cfg)
params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
    "params"
]
# eos_id outside the vocab: no completion can EOS early, so the chunk
# count is deterministic (ceil(23/4)=6 decode chunks) and the assertion
# below cannot flake on a lucky sample from the random init
eng = LMEngine(
    model, cfg, params, max_batch=2, max_seq=96, chunk_steps=4,
    prefill_buckets=(16,), eos_id=cfg.vocab_size + 1, pipeline_depth=1,
).start()
try:
    outs = {}

    def worker(i):
        outs[i] = eng.submit([3 + i, 5, 7, 11], max_new_tokens=24)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(180)
    assert len(outs) == 2 and all(isinstance(v, list) for v in outs.values())
    chunks = eng.stats["chunks"]
    uploads = eng.overlap["carry_uploads"]  # kft_engine_carry_uploads_total
    # the tentpole invariant: steady-state decode pays ZERO per-chunk H2D —
    # carry uploads track admissions (2 here), never chunks
    assert chunks >= 2 and uploads < chunks, (chunks, uploads)
finally:
    eng.stop()
print(f"pipelined decode OK: chunks={chunks} carry_uploads={uploads}")
EOF

echo "== speculative decode: K=4 byte-identical to K=0, fewer forwards =="
python - <<'EOF'
import flax
import jax
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from kubeflow_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, TransformerLM,
)
from kubeflow_tpu.serve.engine import LMEngine  # noqa: E402

cfg = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64, causal=True,
    max_seq_len=256, attn_impl="reference", dtype=jnp.float32,
)
model = TransformerLM(cfg)
params = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))[
    "params"
]
# copy-deterministic stand-in for induction behavior on templated traffic:
# zeroing the attention/MLP write-back makes the greedy chain periodic, so
# prompt-lookup drafts are structurally acceptable (not luck); eos outside
# the vocab keeps the chunk count deterministic
flat = flax.traverse_util.flatten_dict(params)
params = flax.traverse_util.unflatten_dict({
    k: (jnp.zeros_like(v) if k[-2] in ("o_proj", "down_proj") else v)
    for k, v in flat.items()
})
prompt = [5, 9, 13, 7] * 4
results = {}
for k in (0, 4):
    eng = LMEngine(
        model, cfg, params, max_batch=2, max_seq=160, chunk_steps=2,
        prefill_buckets=(16,), eos_id=cfg.vocab_size + 1,
        pipeline_depth=1, spec_draft_tokens=k,
    ).start()
    try:
        toks = eng.submit(prompt, max_new_tokens=64)
        results[k] = (toks, eng.stats["chunks"],
                      eng.stats["spec_accepted"])  # kft_engine_spec_accepted_total
    finally:
        eng.stop()
toks0, chunks0, _ = results[0]
toks4, chunks4, accepted = results[4]
# the tentpole contract: speculation changes the forward count, NEVER the
# token stream — and on repetitive traffic it really accepts
assert toks4 == toks0, (toks4[:8], toks0[:8])
assert accepted > 0, "speculative drafts never accepted"
assert chunks0 >= 1.5 * chunks4, (chunks0, chunks4)
print(f"speculative decode OK: tokens={len(toks4)} identical, "
      f"forwards {chunks0}->{chunks4}, spec_accepted={accepted}")
EOF

echo "== paged kernel (interpret): byte-parity vs gather, int8 pool halved =="
python - <<'EOF'
import jax
import jax.numpy as jnp
import numpy as np  # noqa: E402

from kubeflow_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, TransformerLM,
)
from kubeflow_tpu.serve.engine import LMEngine  # noqa: E402

import dataclasses  # noqa: E402

# the read path is the program's choice: on a CPU the Pallas kernel where
# the configuration asks for the Mosaic interpreter (same semantics), the
# XLA gather where it does not
kernel_cfg = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=1, n_heads=4, d_ff=64, causal=True,
    max_seq_len=128, attn_impl="reference", dtype=jnp.float32,
    interpret_kernels=True,
)
cfg = gather_cfg = dataclasses.replace(kernel_cfg, interpret_kernels=False)
params = TransformerLM(cfg).init(
    jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
)["params"]
prompts = [[3, 5, 7, 11, 13], [2, 4, 6]]


def run(cfg, quant="none"):
    eng = LMEngine(
        TransformerLM(cfg), cfg, params, max_batch=2, max_seq=64,
        chunk_steps=4, prefill_buckets=(16,), eos_id=cfg.vocab_size + 1,
        kv_pool_tokens=16 * 10, page_size=16, kv_quant=quant,
    ).start()
    assert eng.kernel_read == cfg.interpret_kernels
    try:
        outs = [eng.submit(p, max_new_tokens=16) for p in prompts]
        kv = sum(int(lc[w].nbytes)
                 for lc in eng.cache.values() for w in ("k", "v"))
        sc = sum(int(a.nbytes) for lc in eng.cache.values()
                 for w, a in lc.items() if w.endswith("_scale"))
    finally:
        eng.stop()
    return outs, kv, sc


gather, kv_f32, sc_f32 = run(gather_cfg)
kernel, _, _ = run(kernel_cfg)
# the read-path swap is a layout change, not a numerics change
assert kernel == gather, (kernel, gather)
_, kv_int8, sc_int8 = run(gather_cfg, "int8")
# int8 pool = 1/4 of f32 = 1/2 of the bf16 pool the chip serves from;
# per-token-per-head f32 scales are the 1/head_dim overhead on top
assert kv_int8 * 4 == kv_f32 and sc_f32 == 0, (kv_int8, kv_f32)
assert sc_int8 == kv_int8 * 4 // (cfg.d_model // cfg.n_heads)
print(f"paged kernel OK: byte-identical streams, pool {kv_f32}->{kv_int8} B "
      f"(+{sc_int8} B scales)")
EOF

echo "== kill-and-resume: SIGTERM mid-train -> 143 -> exact-step resume =="
python - <<'EOF'
import os, re, signal, subprocess, sys, tempfile, time

tmp = tempfile.mkdtemp(prefix="kft-smoke-preempt-")
ckpt = os.path.join(tmp, "ckpt")
cmd = [
    sys.executable, "-m", "kubeflow_tpu.examples.mnist",
    "--steps", "8", "--global-batch", "16", "--log-every", "1",
    "--checkpoint-dir", ckpt, "--checkpoint-every", "1",
    "--checkpoint-sync",
]
env = {**os.environ, "PYTHONUNBUFFERED": "1",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
log = os.path.join(tmp, "run0.log")
with open(log, "wb") as f:
    proc = subprocess.Popen(cmd, stdout=f, stderr=subprocess.STDOUT, env=env)
    # preemption notice once training demonstrably reached step >= 2
    deadline = time.time() + 180
    while time.time() < deadline:
        text = open(log, errors="replace").read()
        if re.search(r"^step=2 ", text, re.M):
            proc.send_signal(signal.SIGTERM)
            break
        if proc.poll() is not None:
            sys.exit(f"trainer exited early:\n{text}")
        time.sleep(0.1)
    rc = proc.wait(timeout=120)
text = open(log, errors="replace").read()
assert rc == 143, f"expected preemption exit 143, got {rc}:\n{text}"
assert "preempted at step" in text, text

out = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=300)
assert out.returncode == 0, out.stdout + out.stderr
m = re.search(r"resume_step=(\d+)", out.stdout)
assert m, f"no resume marker:\n{out.stdout}"
resume = int(m.group(1))
steps = [int(s) for s in re.findall(r"^step=(\d+) ", out.stdout, re.M)]
assert resume >= 2 and steps == list(range(resume + 1, 9)), (resume, steps)
print(f"kill-and-resume OK: preempted run exited 143, resumed at "
      f"step {resume + 1}, finished 8")
EOF

echo "== quota scheduler: cohort borrow -> preempt -> resume =="
python - <<'EOF'
import sys, tempfile, time

from kubeflow_tpu.obs.prom import REGISTRY
from kubeflow_tpu.orchestrator import (
    JobSpec, LocalCluster, ReplicaSpec, RestartPolicy, RunPolicy,
    SchedulingPolicy, TPURequest,
)
from kubeflow_tpu.orchestrator.resources import Fleet
from kubeflow_tpu.sched import ClusterQueue, LocalQueue, QueueConfig


def counter(name, **labels):
    metric = REGISTRY._metrics.get(name)
    child = metric._children.get(tuple(sorted(labels.items()))) if metric else None
    return child.value if child else 0.0


# tenant-b owns no quota and borrows tenant-a's; exits 143 on SIGTERM
# (the trainer preemption protocol) and finishes clean after the requeue
PREEMPTIBLE = (
    "import os, signal, sys, time;"
    "signal.signal(signal.SIGTERM, lambda *a: sys.exit(143));"
    "time.sleep(30.0 if os.environ['KFT_ATTEMPT'] == '0' else 0.05);"
    "sys.exit(0)"
)
config = QueueConfig(
    [ClusterQueue("tenant-a", {"v5e": 4}, cohort="shared"),
     ClusterQueue("tenant-b", {"v5e": 0}, cohort="shared",
                  borrowing_limit=4)],
    [LocalQueue("team-a", "tenant-a"), LocalQueue("team-b", "tenant-b")],
)


def job(name, queue, code, chips=4):
    return JobSpec(
        name=name,
        replicas={"worker": ReplicaSpec(
            replicas=1, command=(sys.executable, "-c", code),
            restart_policy=RestartPolicy.EXIT_CODE,
            tpu=TPURequest(chips=chips),
        )},
        run_policy=RunPolicy(scheduling=SchedulingPolicy(queue=queue)),
    )


p0 = counter("kft_preemptions_total", reason="borrowed")
r0 = counter("kft_gang_requeues_total", reason="Preempted")
with LocalCluster(
    fleet=Fleet.homogeneous(1, "2x2"),
    base_dir=tempfile.mkdtemp(prefix="kft-smoke-quota-"),
    queues=config, resync_period=0.05, preemption_grace_seconds=10.0,
) as cluster:
    b_uid = cluster.submit(job("borrower", "team-b", PREEMPTIBLE))
    deadline = time.time() + 60
    while time.time() < deadline:
        st = cluster.status(b_uid)
        if st and st.phase == "Running":
            break
        time.sleep(0.02)
    assert cluster.status(b_uid).phase == "Running", "borrower never started"
    # tenant-a reclaims its nominal quota -> tenant-b's borrower preempted
    a_uid = cluster.submit(
        job("reclaimer", "team-a", "import time; time.sleep(0.3)")
    )
    assert cluster.wait(a_uid, timeout=60).phase == "Succeeded"
    b_status = cluster.wait(b_uid, timeout=60)
    assert b_status.phase == "Succeeded", b_status.phase  # resumed + finished
    assert b_status.restart_count == 0, "preemption burned backoff budget"
assert counter("kft_preemptions_total", reason="borrowed") == p0 + 1
assert counter("kft_gang_requeues_total", reason="Preempted") == r0 + 1
print("quota preempt OK: borrower evicted (143), reclaimer ran, "
      "borrower resumed; kft_preemptions_total asserted")
EOF

echo "== gateway: SIGKILL one of two backends mid-burst, zero failures =="
python - <<'EOF'
import json, os, subprocess, sys, tempfile, time, urllib.request

tmp = tempfile.mkdtemp(prefix="kft-smoke-gw-")
isvc = os.path.join(tmp, "isvc.yaml")
with open(isvc, "w") as f:
    f.write(
        "apiVersion: serving.kubeflow.org/v1beta1\n"
        "kind: InferenceService\n"
        "metadata: {name: echo}\n"
        "spec:\n"
        "  predictor:\n"
        "    model:\n"
        "      modelFormat: {name: bert-tiny}\n"
        "      extra: {attn_impl: reference}\n"  # CPU smoke: no pallas
    )
env = {**os.environ, "PYTHONUNBUFFERED": "1"}


def wait_port(pf, proc, log):
    deadline = time.time() + 180
    while time.time() < deadline:
        if os.path.exists(pf) and open(pf).read().strip():
            return int(open(pf).read())
        if proc.poll() is not None:
            sys.exit(f"process died early:\n{open(log, errors='replace').read()}")
        time.sleep(0.1)
    sys.exit("process never bound a port")


procs = []
try:
    ports = []
    for i in range(2):  # two real ModelServer replicas via the CLI
        pf = os.path.join(tmp, f"port{i}")
        log = os.path.join(tmp, f"srv{i}.log")
        p = subprocess.Popen(
            [sys.executable, "-m", "kubeflow_tpu", "serve", "-f", isvc,
             "--http-port", "0", "--port-file", pf],
            stdout=open(log, "wb"), stderr=subprocess.STDOUT, env=env,
        )
        procs.append(p)
        ports.append((pf, p, log))
    ports = [wait_port(pf, p, log) for pf, p, log in ports]

    gw_yaml = os.path.join(tmp, "gw.yaml")
    with open(gw_yaml, "w") as f:  # YAML is a JSON superset
        json.dump({
            "kind": "InferenceGateway", "metadata": {"name": "edge"},
            "spec": {
                "failureThreshold": 2, "probeIntervalS": 2.0,
                "retryBudgetFloor": 30,
                "services": [{"name": "echo", "backends": [
                    f"http://127.0.0.1:{ports[0]}",
                    f"http://127.0.0.1:{ports[1]}",
                ]}],
            },
        }, f)
    gpf = os.path.join(tmp, "gwport")
    gwlog = os.path.join(tmp, "gw.log")
    gw = subprocess.Popen(
        [sys.executable, "-m", "kubeflow_tpu", "gateway", "run",
         "-f", gw_yaml, "--http-port", "0", "--port-file", gpf],
        stdout=open(gwlog, "wb"), stderr=subprocess.STDOUT, env=env,
    )
    procs.append(gw)
    gwport = wait_port(gpf, gw, gwlog)

    def predict(i):
        req = urllib.request.Request(
            f"http://127.0.0.1:{gwport}/v1/models/echo:predict",
            data=json.dumps({"instances": ["the [mask] runs"]}).encode(),
            headers={"Content-Type": "application/json",
                     "x-request-id": f"smoke-{i}"},
        )
        with urllib.request.urlopen(req, timeout=180) as r:
            return json.loads(r.read())

    for i in range(4):  # warm both replicas through the compile
        assert "predictions" in predict(i)

    from kubeflow_tpu.chaos.injectors import kill_backend

    kill_backend(procs[1].pid)  # SIGKILL one replica, burst immediately
    for i in range(20):
        out = predict(100 + i)
        assert "predictions" in out, out

    metrics = urllib.request.urlopen(
        f"http://127.0.0.1:{gwport}/metrics", timeout=30
    ).read().decode()

    def metric(prefix):
        for ln in metrics.splitlines():
            if ln.startswith(prefix):
                return float(ln.rsplit(" ", 1)[1])
        return 0.0

    retries = metric('kft_gateway_retries_total{service="echo"}')
    opens = metric(
        f'kft_gateway_breaker_opens_total{{backend="http://127.0.0.1:{ports[1]}"}}'
    )
    assert retries >= 1, f"no transparent retries observed:\n{metrics}"
    assert opens >= 1, f"breaker never opened for the dead backend:\n{metrics}"
finally:
    for p in procs:
        if p.poll() is None:
            p.kill()
print(f"gateway OK: 20-request burst clean over a dead backend, "
      f"retries={retries:.0f} breaker_opens={opens:.0f}")
EOF

echo "== SRE: wedge an engine behind the gateway; watchdog restarts it, zero failed requests =="
python - <<'EOF'
import asyncio, json, time, urllib.request

import jax, jax.numpy as jnp

from kubeflow_tpu.chaos.injectors import wedge_engine
from kubeflow_tpu.gateway.server import GatewayConfig, InferenceGateway
from kubeflow_tpu.gateway.router import ServiceRoute
from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
from kubeflow_tpu.serve.engine import LMEngineModel
from kubeflow_tpu.serve.model import BucketSpec
from kubeflow_tpu.serve.server import ModelServer

cfg = TransformerConfig(vocab_size=89, d_model=32, n_layers=2, n_heads=4,
                        d_ff=64, causal=True, max_seq_len=256,
                        attn_impl="reference", dtype=jnp.float32)
tlm = TransformerLM(cfg)
params = tlm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


def replica():
    m = LMEngineModel(
        "m", None, config=cfg, max_batch=4, chunk_steps=2,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
        max_new_tokens=6, eos_id=1, watchdog_interval_s=0.1,
        watchdog_min_wedge_s=60.0,
    )
    m.load()
    m._params = jax.device_put(params)
    m.engine.stop()
    m.engine = m._make_engine().start()
    return m


async def main():
    m_a, m_b = replica(), replica()
    ms_a = ModelServer([m_a], http_port=0)
    ms_b = ModelServer([m_b], http_port=0)
    await ms_a.start_async()
    await ms_b.start_async()

    def port_of(ms):
        (site,) = ms._runner.sites
        return site._server.sockets[0].getsockname()[1]

    pa, pb = port_of(ms_a), port_of(ms_b)
    gw = InferenceGateway(GatewayConfig(
        probe_interval_s=0.25, eject_threshold=1, failure_threshold=2,
        recovery_s=60.0, retry_budget_floor=100,
        routes=[ServiceRoute(name="m", max_attempts=4)],
        backends=[("m", f"http://127.0.0.1:{pa}", "default"),
                  ("m", f"http://127.0.0.1:{pb}", "default")],
    ), http_port=0)
    await gw.start_async()
    loop = asyncio.get_running_loop()

    def predict(i, extra=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{gw.http_port}/v1/models/m:predict",
            data=json.dumps(
                {"instances": [{"input_ids": [3 + i % 5, 4, 5]}]}
            ).encode(),
            headers={"Content-Type": "application/json",
                     "x-request-id": f"sre-{i}", **(extra or {})},
        )
        try:
            with urllib.request.urlopen(req, timeout=180) as r:
                return r.status, dict(r.headers)
        except urllib.error.HTTPError as e:
            return e.code, dict(e.headers)

    async def one(i, extra=None):
        return await loop.run_in_executor(None, predict, i, extra)

    try:
        for i in range(6):  # warm both replicas through their compiles
            status, _ = await one(i)
            assert status == 200, status
        for m in (m_a, m_b):
            m.watchdog.config.min_wedge_s = 1.0

        release = wedge_engine(m_a.engine, hold_s=45.0)
        results = await asyncio.gather(*[one(100 + i) for i in range(16)])
        release()
        statuses = [s for s, _ in results]
        assert statuses == [200] * 16, statuses

        # blocking reads must leave the loop thread: the backends are
        # served BY this loop, so an inline urlopen would deadlock
        metrics = (await loop.run_in_executor(
            None,
            lambda: urllib.request.urlopen(
                f"http://127.0.0.1:{pa}/metrics", timeout=30
            ).read(),
        )).decode()
        trips = 0.0
        for ln in metrics.splitlines():
            if ln.startswith('kft_engine_watchdog_trips_total{model="m",reason="wedged"}'):
                trips = float(ln.rsplit(" ", 1)[1])
        assert trips >= 1, f"watchdog never tripped:\n{metrics}"
        assert m_a.ready and m_b.ready

        # correctly-shed tail: an expired deadline is 503 + Retry-After
        status, hdrs = await one(999, {"x-kft-deadline-ms": "0"})
        assert status == 503 and hdrs.get("Retry-After"), (status, hdrs)
        print(f"SRE OK: wedge mid-burst absorbed — watchdog trips={trips:.0f}, "
              "16/16 requests clean, deadline shed 503+Retry-After")
    finally:
        await gw.stop_async()
        m_a.unload()
        m_b.unload()
        await ms_a.stop_async()
        await ms_b.stop_async()

asyncio.run(main())
EOF

echo "== mid-stream failover: kill the streaming replica, client sees one unbroken stream =="
python - <<'EOF'
import asyncio, json, urllib.request

import jax, jax.numpy as jnp

from kubeflow_tpu.chaos.injectors import kill_mid_stream
from kubeflow_tpu.gateway.router import ServiceRoute
from kubeflow_tpu.gateway.server import GatewayConfig, InferenceGateway
from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
from kubeflow_tpu.serve.engine import LMEngineModel
from kubeflow_tpu.serve.model import BucketSpec
from kubeflow_tpu.serve.server import ModelServer
from kubeflow_tpu.serve.watchdog import EngineRestarting

cfg = TransformerConfig(vocab_size=89, d_model=32, n_layers=2, n_heads=4,
                        d_ff=64, causal=True, max_seq_len=256,
                        attn_impl="reference", dtype=jnp.float32)
tlm = TransformerLM(cfg)
params = tlm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


def replica():
    m = LMEngineModel(
        "m", None, config=cfg, max_batch=4, chunk_steps=2,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
        max_new_tokens=6, eos_id=1, watchdog_interval_s=0.1,
        watchdog_min_wedge_s=60.0,
    )
    m.load()
    m._params = jax.device_put(params)
    m.engine.stop()
    m.engine = m._make_engine().start()
    return m


async def main():
    m_a, m_b = replica(), replica()
    ms_a = ModelServer([m_a], http_port=0)
    ms_b = ModelServer([m_b], http_port=0)
    await ms_a.start_async()
    await ms_b.start_async()

    def port_of(ms):
        (site,) = ms._runner.sites
        return site._server.sockets[0].getsockname()[1]

    pa, pb = port_of(ms_a), port_of(ms_b)
    url_a, url_b = (f"http://127.0.0.1:{p}" for p in (pa, pb))
    # session affinity pins the stream to one replica, so the victim is
    # deterministic and the resume provably lands on the peer
    route = ServiceRoute(name="m", affinity="session", max_attempts=4)
    gw = InferenceGateway(GatewayConfig(
        probe_interval_s=0.25, failure_threshold=2, recovery_s=60.0,
        retry_budget_floor=100, routes=[route],
        backends=[("m", url_a, "default"), ("m", url_b, "default")],
    ), http_port=0)
    await gw.start_async()
    loop = asyncio.get_running_loop()

    def stream(req_id):
        req = urllib.request.Request(
            f"http://127.0.0.1:{gw.http_port}/v2/models/m/generate_stream",
            data=json.dumps({"input_ids": [3, 4, 5]}).encode(),
            headers={"Content-Type": "application/json",
                     "x-session-id": "smoke-s1", "x-request-id": req_id},
        )
        with urllib.request.urlopen(req, timeout=180) as r:
            text = r.read().decode()
        return [json.loads(ln[6:]) for ln in text.splitlines()
                if ln.startswith("data: ")]

    def predict(i):
        req = urllib.request.Request(
            f"http://127.0.0.1:{gw.http_port}/v1/models/m:predict",
            data=json.dumps(
                {"instances": [{"input_ids": [3 + i % 5, 4, 5]}]}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=180) as r:
            return r.status

    def metric(line_prefix):
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{gw.http_port}/metrics", timeout=30
        ).read().decode()
        for ln in text.splitlines():
            if ln.startswith(line_prefix):
                return float(ln.rsplit(" ", 1)[1])
        return 0.0

    try:
        for i in range(4):  # warm both replicas through their compiles
            assert await loop.run_in_executor(None, predict, i) == 200
        base = await loop.run_in_executor(None, stream, "smoke-base")
        assert all("error" not in f for f in base), base
        base_toks = [t for f in base for t in f.get("token_ids", [])]

        victim_b = gw._affine_pick(route, "default", "session:smoke-s1")
        victim, peer = (m_a, m_b) if victim_b.url == url_a else (m_b, m_a)
        kill_mid_stream(
            victim.engine, after_tokens=2,
            action=lambda eng: eng.poison(
                EngineRestarting("smoke: replica killed mid-stream")
            ),
        )
        frames = await loop.run_in_executor(None, stream, "smoke-failover")
        assert all("error" not in f for f in frames), frames
        toks = [t for f in frames for t in f.get("token_ids", [])]
        assert toks == base_toks, (toks, base_toks)
        assert frames[-1]["done"] and frames[-1]["n_tokens"] == len(base_toks)
        resumes = await loop.run_in_executor(
            None, metric,
            'kft_gateway_stream_resumes_total{outcome="ok",service="m"}')
        assert resumes >= 1, "no successful stream resume recorded"
        assert peer.engine.stats["resume_admits"] >= 1
        print(f"mid-stream failover OK: {len(toks)} tokens unbroken across "
              f"a replica kill, stream_resumes_ok={resumes:.0f}")
    finally:
        await gw.stop_async()
        m_a.unload()
        m_b.unload()
        await ms_a.stop_async()
        await ms_b.stop_async()

asyncio.run(main())
EOF

echo "== autoscaler burst: 1->3->1->0 scale cycle, zero failures, prefix-KV transfer =="
python - <<'EOF'
import asyncio, json, time, urllib.request

import jax, jax.numpy as jnp

from kubeflow_tpu.autoscale import (
    GatewaySignalSource, KPAConfig, ReplicaFleet, ServingAutoscaler,
)
from kubeflow_tpu.gateway.router import ServiceRoute
from kubeflow_tpu.gateway.server import GatewayConfig, InferenceGateway
from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
from kubeflow_tpu.obs.prom import REGISTRY
from kubeflow_tpu.serve.engine import LMEngineModel
from kubeflow_tpu.serve.model import BucketSpec
from kubeflow_tpu.serve.server import ModelServer

cfg = TransformerConfig(vocab_size=89, d_model=32, n_layers=2, n_heads=4,
                        d_ff=64, causal=True, max_seq_len=256,
                        attn_impl="reference", dtype=jnp.float32)
tlm = TransformerLM(cfg)
params = tlm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


def metric(name, **labels):
    m = REGISTRY._metrics.get(name)
    child = m._children.get(tuple(sorted(labels.items()))) if m else None
    return child.value if child else 0.0


async def main():
    servers = {}

    async def launch(index):
        m = LMEngineModel(
            "m", None, config=cfg, max_batch=4, chunk_steps=2,
            buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
            max_new_tokens=24, eos_id=cfg.vocab_size + 1, watchdog=False,
            prefix_cache_entries=32,
        )
        m.load()
        m._params = jax.device_put(params)  # identical weights per replica
        m.engine.stop()
        m.engine = m._make_engine().start()
        ms = ModelServer([m], http_port=0)
        await ms.start_async()
        (site,) = ms._runner.sites
        port = site._server.sockets[0].getsockname()[1]
        url = f"http://127.0.0.1:{port}"

        async def stop():
            m.unload()
            await ms.stop_async()

        servers[url] = (m, ms)
        return url, stop

    asc = ServingAutoscaler(tick_interval_s=0.15)
    gw = InferenceGateway(GatewayConfig(
        probe_interval_s=0.25, activation_timeout_s=60.0,
        routes=[ServiceRoute(name="m")],
    ), scale_up=asc.kick)
    fleet = ReplicaFleet("m", launch, pool=gw.pool, model="m")
    source = GatewaySignalSource(gw, "m")
    asc.add_service("m", KPAConfig(
        target=1.0, min_replicas=0, max_replicas=3,
        stable_window_s=3.0, panic_window_s=0.6, panic_threshold=1.5,
        max_scale_down_rate=2.0, scale_to_zero_grace_s=1.2,
    ), source, fleet)
    await fleet.scale_to(1)
    await gw.start_async()
    loop = asyncio.get_running_loop()
    prompts = [[2 + (7 * i + j) % 80 for j in range(17)] for i in range(10)]

    def predict(i):
        req = urllib.request.Request(
            f"http://127.0.0.1:{gw.http_port}/v1/models/m:predict",
            data=json.dumps(
                {"instances": [{"input_ids": prompts[i % len(prompts)]}]}
            ).encode(),
            headers={"Content-Type": "application/json",
                     "x-request-id": f"burst-{i}"},
        )
        with urllib.request.urlopen(req, timeout=180) as r:
            return r.status

    try:
        for i in range(3):  # warm replica 0 through its compiles
            assert await loop.run_in_executor(None, predict, i) == 200
        asc.start()
        peak = [fleet.current()]

        async def watch():
            while True:
                peak[0] = max(peak[0], fleet.current())
                await asyncio.sleep(0.03)

        watcher = asyncio.ensure_future(watch())
        # open-loop burst: fixed arrivals, nobody waits on responses
        tasks = []
        for i in range(40):
            tasks.append(loop.run_in_executor(None, predict, 100 + i))
            await asyncio.sleep(0.025)
        statuses = await asyncio.gather(*tasks)
        assert statuses == [200] * 40, statuses
        deadline = time.monotonic() + 90
        while peak[0] < 3 and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        assert peak[0] == 3, f"never panicked to 3 (peak {peak[0]})"
        moved = fleet.stats["kv_entries_moved"]
        assert moved >= 1, "scale-up replicas pulled no prefix KV"
        # quiet: stable window drains, grace expires, replicas -> 0
        deadline = time.monotonic() + 90
        while fleet.current() > 0 and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        assert fleet.current() == 0, fleet.current()
        watcher.cancel()
        # scale-from-zero: parked in the activator, kick relaunches
        acts0 = metric("kft_gateway_activations_total", service="m")
        assert await loop.run_in_executor(None, predict, 999) == 200
        assert fleet.current() == 1
        assert metric("kft_gateway_activations_total", service="m") == acts0 + 1
        print(f"autoscaler OK: 40-request burst 1->3 (panic), idle ->0, "
              f"cold request served via activator; prefix-KV entries "
              f"moved={moved}, "
              f"scale_events_up="
              f"{metric('kft_autoscaler_scale_events_total', service='m', direction='up'):.0f}")
    finally:
        await asc.stop()
        await source.close()
        await fleet.close()
        await gw.stop_async()

asyncio.run(main())
EOF

echo "== tracing: one trace id gateway edge -> decode chunk, TTFT/TPOT histograms, Perfetto export =="
python - <<'EOF'
import asyncio, json, urllib.request

import jax, jax.numpy as jnp

from kubeflow_tpu.gateway.router import ServiceRoute
from kubeflow_tpu.gateway.server import GatewayConfig, InferenceGateway
from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
from kubeflow_tpu.obs.trace import TRACER, TraceContext, to_perfetto
from kubeflow_tpu.serve.engine import LMEngineModel
from kubeflow_tpu.serve.model import BucketSpec
from kubeflow_tpu.serve.server import ModelServer

cfg = TransformerConfig(vocab_size=89, d_model=32, n_layers=2, n_heads=4,
                        d_ff=64, causal=True, max_seq_len=256,
                        attn_impl="reference", dtype=jnp.float32)
tlm = TransformerLM(cfg)
params = tlm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


def replica():
    m = LMEngineModel(
        "m", None, config=cfg, max_batch=4, chunk_steps=2,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
        max_new_tokens=6, eos_id=1,
    )
    m.load()
    m._params = jax.device_put(params)
    m.engine.stop()
    m.engine = m._make_engine().start()
    return m


async def main():
    TRACER.sample_every = 1  # keep every trace in this tiny burst
    m_a, m_b = replica(), replica()
    ms_a = ModelServer([m_a], http_port=0)
    ms_b = ModelServer([m_b], http_port=0)
    await ms_a.start_async()
    await ms_b.start_async()

    def port_of(ms):
        (site,) = ms._runner.sites
        return site._server.sockets[0].getsockname()[1]

    pa, pb = port_of(ms_a), port_of(ms_b)
    gw = InferenceGateway(GatewayConfig(
        probe_interval_s=0.25,
        routes=[ServiceRoute(name="m")],
        backends=[("m", f"http://127.0.0.1:{pa}", "default"),
                  ("m", f"http://127.0.0.1:{pb}", "default")],
    ), http_port=0)
    await gw.start_async()
    loop = asyncio.get_running_loop()
    ctx = TraceContext("ab" * 16, "cd" * 8)  # the "client SDK" span

    def predict(i, extra=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{gw.http_port}/v1/models/m:predict",
            data=json.dumps(
                {"instances": [{"input_ids": [3 + i % 5, 4, 5]}]}
            ).encode(),
            headers={"Content-Type": "application/json", **(extra or {})},
        )
        with urllib.request.urlopen(req, timeout=180) as r:
            return r.status

    def fetch(url):
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.read().decode()

    try:
        for i in range(6):
            assert await loop.run_in_executor(None, predict, i) == 200
        assert await loop.run_in_executor(
            None, predict, 99, {"x-kft-trace": ctx.header()}) == 200

        # the client-stamped trace covers EVERY hop, edge to decode chunk
        snap = TRACER.snapshot(limit=64)
        tr = next(t for t in snap["traces"] if t["trace_id"] == ctx.trace_id)
        names = {s["name"] for s in tr["spans"]}
        need = {"route", "proxy", "dataplane", "engine",
                "queue.wait", "prefill", "decode.chunk"}
        assert need <= names, f"span tree incomplete: {sorted(names)}"
        route = next(s for s in tr["spans"] if s["name"] == "route")
        assert route["parent_span_id"] == ctx.span_id

        # the replica's own /debug/traces serves its half of the story
        replica_snap = None
        for port in (pa, pb):
            doc = json.loads(await loop.run_in_executor(
                None, fetch, f"http://127.0.0.1:{port}/debug/traces?limit=64"))
            hit = [t for t in doc["traces"] if t["trace_id"] == ctx.trace_id]
            if hit:
                replica_snap = hit[0]
        assert replica_snap is not None, "trace missing from /debug/traces"
        assert any(s["name"] == "decode.chunk" for s in replica_snap["spans"])

        # Perfetto conversion round-trips through JSON
        perfetto = to_perfetto(snap)
        assert any(e.get("ph") == "X" for e in json.loads(
            json.dumps(perfetto))["traceEvents"])

        # completed streams fed the TTFT/TPOT histograms
        ttft = tpot = 0.0
        for port in (pa, pb):
            for ln in (await loop.run_in_executor(
                    None, fetch, f"http://127.0.0.1:{port}/metrics")).splitlines():
                if ln.startswith('kft_server_ttft_ms_count{model="m"}'):
                    ttft += float(ln.rsplit(" ", 1)[1])
                if ln.startswith('kft_server_tpot_ms_count{model="m"}'):
                    tpot += float(ln.rsplit(" ", 1)[1])
        assert ttft >= 1, f"TTFT observations missing: {ttft}"
        assert tpot >= 1, f"TPOT observations missing: {tpot}"
        print(f"tracing OK: {len(tr['spans'])} spans edge->decode under one "
              f"trace id, ttft_count={ttft:.0f} tpot_count={tpot:.0f}, "
              f"perfetto events={len(perfetto['traceEvents'])}")
    finally:
        await gw.stop_async()
        m_a.unload()
        m_b.unload()
        await ms_a.stop_async()
        await ms_b.stop_async()

asyncio.run(main())
EOF

echo "== disaggregated serving: prefill pool -> KV ship -> decode pool, zero decode-side prefill =="
python - <<'EOF'
import asyncio, json, urllib.request

import jax, jax.numpy as jnp

from kubeflow_tpu.gateway.router import ServiceRoute
from kubeflow_tpu.gateway.server import GatewayConfig, InferenceGateway
from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
from kubeflow_tpu.serve.engine import LMEngineModel
from kubeflow_tpu.serve.model import BucketSpec
from kubeflow_tpu.serve.server import ModelServer

cfg = TransformerConfig(vocab_size=89, d_model=32, n_layers=2, n_heads=4,
                        d_ff=64, causal=True, max_seq_len=256,
                        attn_impl="reference", dtype=jnp.float32)
tlm = TransformerLM(cfg)
params = tlm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


def replica():
    m = LMEngineModel(
        "m", None, config=cfg, max_batch=4, chunk_steps=2,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
        max_new_tokens=6, eos_id=1,
    )
    m.load()
    m._params = jax.device_put(params)
    m.engine.stop()
    m.engine = m._make_engine().start()
    return m


async def main():
    m_pre, m_dec = replica(), replica()
    ms_pre = ModelServer([m_pre], http_port=0, role="prefill")
    ms_dec = ModelServer([m_dec], http_port=0, role="decode")
    await ms_pre.start_async()
    await ms_dec.start_async()

    def port_of(ms):
        (site,) = ms._runner.sites
        return site._server.sockets[0].getsockname()[1]

    pp, pd = port_of(ms_pre), port_of(ms_dec)
    gw = InferenceGateway(GatewayConfig(
        probe_interval_s=0.25,
        routes=[ServiceRoute(name="m")],
        backends=[("m", f"http://127.0.0.1:{pp}", "default", "prefill"),
                  ("m", f"http://127.0.0.1:{pd}", "default", "decode")],
    ), http_port=0)
    await gw.start_async()
    loop = asyncio.get_running_loop()
    prompts = [[3 + i, 9, 11, 5, 7, 2 + i, 13, 8] for i in range(3)]

    def generate(url, ids):
        req = urllib.request.Request(
            url, data=json.dumps({"input_ids": ids}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=180) as r:
            return json.loads(r.read().decode())

    def metric(port, name):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
            for ln in r.read().decode().splitlines():
                if ln.startswith(name + "{") or ln.startswith(name + " "):
                    return float(ln.rsplit(" ", 1)[1])
        return 0.0

    try:
        # the prefill-role replica is NOT traffic-selectable: the gateway
        # must route every client request to the decode backend
        state = gw.state_view()
        roles = {b["url"]: b["role"] for b in state["services"][0]["backends"]}
        assert set(roles.values()) == {"prefill", "decode"}, roles
        via_gw = [
            await loop.run_in_executor(
                None, generate, f"http://127.0.0.1:{gw.http_port}"
                f"/v2/models/m/generate", p)
            for p in prompts
        ]
        # colocated reference: the same prompts straight at the prefill
        # replica (a full server; role only gates gateway selection)
        direct = [
            await loop.run_in_executor(
                None, generate, f"http://127.0.0.1:{pp}/v2/models/m/generate",
                p)
            for p in prompts
        ]
        assert via_gw == direct, (via_gw, direct)

        # the acceptance criterion, metric-asserted off the decode
        # replica: every span was injected, ZERO prefill chunks executed
        # (metric() blocks, and the servers live on THIS loop: executor)
        async def g(port, name):
            return await loop.run_in_executor(None, metric, port, name)
        assert await g(pd, "kubeflow_tpu_engine_prefill_pieces") == 0
        assert await g(pd, "kubeflow_tpu_engine_kv_injected") == 3
        assert await g(pd, "kubeflow_tpu_engine_kv_ship_bytes") > 0
        assert await g(pd, "kubeflow_tpu_engine_kv_ship_fallbacks") == 0
        assert await g(pp, "kubeflow_tpu_engine_kv_spans_exported") == 3
        ship = await g(pd, "kubeflow_tpu_engine_kv_ship_bytes")
        print(f"disagg OK: 3 generates via gateway == colocated tokens, "
              f"decode prefill_pieces=0, kv_injected=3, "
              f"ship_bytes={ship:.0f}")
    finally:
        await gw.stop_async()
        m_pre.unload()
        m_dec.unload()
        await ms_pre.stop_async()
        await ms_dec.stop_async()

asyncio.run(main())
EOF

echo "== loadgen: seeded open-loop goodput 1.0, then wedged-replica dip with zero client failures =="
python - <<'EOF'
import asyncio
import dataclasses

from kubeflow_tpu.chaos.plan import FaultPlan, WedgeEngine
from kubeflow_tpu.loadgen import ChaosOverlay, TenantSpec, WorkloadMix
from kubeflow_tpu.loadgen.harness import HarnessConfig, run_serving_load

# the bench recipe (bench.py serving_load), shortened: a generous WIRE
# deadline (tight ones are unmeetable on CPU and surface as in-stream
# errors) with a tight ACCOUNTING slo, so a wedge shows up as
# completed_late — a goodput dip — never as a client-visible failure
mix = WorkloadMix(
    prompt_lens=(6, 10), output_lens=(4, 8),
    tenants=(
        TenantSpec("interactive", weight=2.0, priority=2,
                   deadline_ms=30_000.0, slo_ms=2_000.0),
        TenantSpec("batch", weight=1.0, adapter="batch-v1",
                   slo_ms=2_000.0),
    ),
    vocab=80, seed=7,
)
steady_cfg = HarnessConfig(
    seed=7, process="poisson", rate_rps=4.0, duration_s=7.0, mix=mix,
    initial_replicas=2, max_replicas=2, min_replicas=2,
)

steady = asyncio.run(run_serving_load(steady_cfg))
g = steady["goodput"]["overall"]
assert g["offered"] > 0, steady["run"]
assert g["error"] == 0, g
assert g["goodput"] == 1.0, g
# server-side histograms (PR 15), baseline-subtracted: the run's own
# traffic must be there, not just warmup's
ttft, tpot = steady["latency"]["ttft_ms"], steady["latency"]["tpot_ms"]
assert ttft["count"] > 0 and ttft["p50"] is not None, ttft
assert tpot["count"] > 0, tpot

chaos_cfg = dataclasses.replace(steady_cfg, duration_s=8.0, chaos=ChaosOverlay(
    plan=FaultPlan((WedgeEngine(model="m", hold_s=3.0),), seed=7),
    at_s=3.0, window_s=5.0,
))
chaos = asyncio.run(run_serving_load(chaos_cfg))
c = chaos["chaos"]
assert c["faults"] == ["WedgeEngine"], c
assert c["client_visible_failures"] == 0, c
assert c["goodput_dip"] is not None and c["goodput_dip"] > 0, c
print(f"loadgen OK: steady goodput={g['goodput']} over {g['offered']} "
      f"(ttft_p50={ttft['p50']:.1f}ms n={ttft['count']}), wedge dip="
      f"{c['goodput_dip']} in {c['window_s']}, zero client failures")
EOF

echo "smoke OK"
