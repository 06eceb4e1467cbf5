"""BASELINE benchmark suite — all five BASELINE.json configs, measured.

The reference publishes no numbers (BASELINE.md), so both sides are measured
here: OUR side on the local accelerator (TPU v5e under the driver), the
REFERENCE side in-process with torch-CPU / framework-native equivalents of
each config's data plane (the reference examples run CPU/gloo in CI —
SURVEY.md §4, §6).

Emits one JSON line per config as it completes, then ONE final headline line
(the driver parses the last line):

    {"metric": "bert_base_train_mfu", "value": <pct>, "unit": "%",
     "vs_baseline": <speedup>, "detail": {... all five configs ...}}

Configs (BASELINE.json `configs[0..4]` / SURVEY.md §6 rows 1-5):
  1. mnist_cnn_train_step_time   — JAXJob-vs-PyTorchJob MNIST (median±IQR,
                                   steady-state drift check)
  2. resnet50_train_throughput   — ResNet-50 CIFAR-10 DP step
  3. bert_base_train_step_time   — BERT-base MLM step with **MFU** from
                                   analytic FLOPs vs v5e bf16 peak
  4. katib_trials_to_goal        — 16 parallel gang-scheduled trials on a
                                   simulated 4-slice fleet, bayesian vs
                                   random TRIALS-to-goal (wall time is
                                   host-noise; trials are the chip cost)
  5. kserve_bert_p50_latency     — p50/p99 + cold-start through the real
                                   ModelServer over REST and gRPC
"""

from __future__ import annotations

import asyncio
import json
import statistics
import sys
import time

#: Per-chip peaks keyed by ``device_kind`` as JAX reports it. Source:
#: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).
#: A device that is not in the table is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def device_peaks() -> dict:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise RuntimeError(
            f"no published peaks for device_kind {kind!r}; "
            f"known: {sorted(DEVICE_PEAKS)}"
        )
    return DEVICE_PEAKS[kind]


def _median_iqr(times_s: list[float]) -> tuple[float, float]:
    ms = sorted(t * 1e3 for t in times_s)
    n = len(ms)
    med = statistics.median(ms)
    iqr = ms[(3 * n) // 4] - ms[n // 4]
    return med, iqr


def _chained_step_times(
    step_fn, state, batches, *, reps: int = 5, n_small: int = 5, n_large: int = 20
):
    """Per-step seconds from chained runs.

    Steps chain through the donated train state, so timing a dependent run
    of N steps ended by a HOST TRANSFER of a metric scalar (a transfer
    cannot complete before the compute producing it) is exact up to one
    constant sync cost — which the two-point difference
    t(n_large) - t(n_small) cancels. Returns (state, per-step estimates).
    """
    import jax
    import numpy as np

    def run(n, state, k0):
        t0 = time.perf_counter()
        m = None
        for i in range(n):
            state, m = step_fn(state, batches[(k0 + i) % len(batches)])
        np.asarray(jax.tree_util.tree_leaves(m)[0])  # sync: host transfer
        return time.perf_counter() - t0, state

    estimates, k = [], 0
    for _ in range(reps):
        t_small, state = run(n_small, state, k)
        k += n_small
        t_large, state = run(n_large, state, k)
        k += n_large
        estimates.append((t_large - t_small) / (n_large - n_small))
    return state, estimates


def _steady_state_drift(times_s: list[float]) -> float:
    """|median(2nd half) - median(1st half)| / median, as a fraction."""
    h = len(times_s) // 2
    a = statistics.median(times_s[:h])
    b = statistics.median(times_s[h:])
    return abs(b - a) / statistics.median(times_s)


# --------------------------------------------------------------------------- #
# config 1: MNIST CNN train step (JAXJob vs PyTorchJob/gloo-CPU analog)
# --------------------------------------------------------------------------- #

MNIST_BATCH = 64


def bench_mnist() -> dict:
    import jax
    import optax

    from kubeflow_tpu.core.mesh import MeshSpec
    from kubeflow_tpu.data.synthetic import (
        ClassPrototypeDataset,
        local_shard_iterator,
    )
    from kubeflow_tpu.models.mnist_cnn import MnistCNN, make_init_fn, make_loss_fn
    from kubeflow_tpu.train.loop import TrainConfig, Trainer

    warmup = 10
    model = MnistCNN()
    trainer = Trainer(
        init_params=make_init_fn(model),
        loss_fn=make_loss_fn(model),
        optimizer=optax.adam(1e-3),
        config=TrainConfig(
            mesh=MeshSpec.data_parallel(jax.device_count()),
            global_batch=MNIST_BATCH,
            steps=1000,
            log_every=10_000,
        ),
    )
    state = trainer.init_state()
    step_fn = trainer._build_step(state)
    data = local_shard_iterator(ClassPrototypeDataset(), MNIST_BATCH)
    batches = [trainer.global_batch_array(next(data)) for _ in range(8)]

    for i in range(warmup):
        state, m = step_fn(state, batches[i % len(batches)])
    import numpy as np

    np.asarray(jax.tree_util.tree_leaves(m)[0])

    state, times = _chained_step_times(
        step_fn, state, batches, reps=7, n_small=10, n_large=40
    )
    med, iqr = _median_iqr(times)
    drift = _steady_state_drift(times)

    torch_ms = _torch_mnist_ms()
    return {
        "metric": "mnist_cnn_train_step_time",
        "value": round(med, 4),
        "unit": "ms",
        "vs_baseline": round(torch_ms / med, 3),
        "detail": {
            "iqr_ms": round(iqr, 4),
            "steady_state_drift": round(drift, 4),
            "steady": drift < 0.25,
            "timing": "chained two-point (see _chained_step_times)",
            "global_batch": MNIST_BATCH,
            "reference_torch_cpu_ms": round(torch_ms, 4),
        },
    }


def _torch_mnist_ms() -> float:
    import numpy as np
    import torch
    import torch.nn as nn
    import torch.nn.functional as F

    from kubeflow_tpu.data.synthetic import ClassPrototypeDataset

    torch.manual_seed(0)

    class Net(nn.Module):
        def __init__(self):
            super().__init__()
            self.c1 = nn.Conv2d(1, 32, 3, padding=1)
            self.c2 = nn.Conv2d(32, 64, 3, padding=1)
            self.f1 = nn.Linear(64 * 7 * 7, 128)
            self.f2 = nn.Linear(128, 10)

        def forward(self, x):
            x = F.max_pool2d(F.relu(self.c1(x)), 2)
            x = F.max_pool2d(F.relu(self.c2(x)), 2)
            return self.f2(F.relu(self.f1(x.flatten(1))))

    ds = ClassPrototypeDataset()
    net = Net()
    opt = torch.optim.Adam(net.parameters(), lr=1e-3)

    def step(i):
        x, y = ds.batch(MNIST_BATCH, step=i)
        xt = torch.from_numpy(np.transpose(x, (0, 3, 1, 2)))
        yt = torch.from_numpy(y.astype(np.int64))
        opt.zero_grad()
        F.cross_entropy(net(xt), yt).backward()
        opt.step()

    for i in range(3):
        step(i)
    times = []
    for i in range(12):
        t0 = time.perf_counter()
        step(i)
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e3


# --------------------------------------------------------------------------- #
# config 3: BERT-base MLM train step + MFU (MPIJob-Horovod-allreduce analog)
# --------------------------------------------------------------------------- #

BERT_BATCH = 32
BERT_SEQ = 128


def bert_train_flops_per_step(
    batch: int, seq: int, *, layers=12, hidden=768, inter=3072, vocab=30522
) -> float:
    """Analytic matmul FLOPs for one BertForMaskedLM train step.

    fwd = 2·S·P_matmul + 4·L·S²·H  (QKᵀ and AV, bidirectional — no causal
    halving), train = 3×fwd (backward re-does each matmul twice). Embedding
    gathers and normalizations excluded — they don't ride the MXU.
    """
    p_matmul = layers * (4 * hidden * hidden + 2 * hidden * inter)
    p_head = hidden * hidden + hidden * vocab  # mlm_transform + unembed
    fwd = 2 * seq * (p_matmul + p_head) + 4 * layers * seq * seq * hidden
    return 3.0 * batch * fwd


def bench_bert() -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from kubeflow_tpu.core.mesh import MeshSpec
    from kubeflow_tpu.data.synthetic import TokenLMDataset, local_shard_iterator
    from kubeflow_tpu.models.bert import (
        BertForMaskedLM,
        bert_base,
        make_mlm_init_fn,
        make_mlm_loss_fn,
    )
    from kubeflow_tpu.train.loop import TrainConfig, Trainer

    warmup = 5
    on_tpu = jax.default_backend() == "tpu"
    cfg = bert_base(
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        attn_impl="flash" if on_tpu else "reference",
    )
    model = BertForMaskedLM(cfg)
    trainer = Trainer(
        init_params=make_mlm_init_fn(model, BERT_SEQ, BERT_BATCH),
        loss_fn=make_mlm_loss_fn(model),
        optimizer=optax.adamw(1e-4),
        config=TrainConfig(
            mesh=MeshSpec.data_parallel(jax.device_count()),
            global_batch=BERT_BATCH,
            steps=1000,
            log_every=10_000,
        ),
    )
    state = trainer.init_state()
    step_fn = trainer._build_step(state)
    ds = TokenLMDataset(vocab_size=cfg.vocab_size, seq_len=BERT_SEQ)
    data = local_shard_iterator(ds, BERT_BATCH)
    batches = [trainer.global_batch_array(next(data)) for _ in range(4)]

    import numpy as np

    for i in range(warmup):
        state, m = step_fn(state, batches[i % len(batches)])
    np.asarray(jax.tree_util.tree_leaves(m)[0])
    state, times = _chained_step_times(
        step_fn, state, batches, reps=5, n_small=5, n_large=20
    )
    med, iqr = _median_iqr(times)

    flops = bert_train_flops_per_step(BERT_BATCH, BERT_SEQ)
    achieved = flops / (med / 1e3)
    # peak scales with the device count the DP mesh spans
    peak = device_peaks()["bf16_flops"] * jax.device_count()
    mfu = achieved / peak

    torch_ms, torch_batch = _torch_bert_ms()
    # normalize per-sequence: the CPU side can't run the TPU batch size
    speedup = (torch_ms / torch_batch) / (med / BERT_BATCH)
    return {
        "metric": "bert_base_train_step_time",
        "value": round(med, 3),
        "unit": "ms",
        "vs_baseline": round(speedup, 3),
        "detail": {
            "iqr_ms": round(iqr, 3),
            "global_batch": BERT_BATCH,
            "seq_len": BERT_SEQ,
            "dtype": "bfloat16" if on_tpu else "float32",
            "attn_impl": cfg.attn_impl,
            "analytic_tflops_per_step": round(flops / 1e12, 3),
            "achieved_tflops_per_s": round(achieved / 1e12, 2),
            "mfu_pct_vs_v5e_peak": round(mfu * 100, 2),
            "steady_state_drift": round(_steady_state_drift(times), 4),
            "reference_torch_cpu_ms": round(torch_ms, 2),
            "reference_torch_batch": torch_batch,
            "speedup_is_per_sequence": True,
        },
    }


def _torch_bert_ms() -> tuple[float, int]:
    """Reference side: HF torch BertForMaskedLM train step on CPU."""
    import torch
    from transformers import BertConfig as HFConfig
    from transformers import BertForMaskedLM as HFBert

    torch.manual_seed(0)
    batch = 4
    net = HFBert(HFConfig())  # bert-base-uncased dimensions, random init
    opt = torch.optim.AdamW(net.parameters(), lr=1e-4)
    ids = torch.randint(0, 30522, (batch, BERT_SEQ))

    def step():
        opt.zero_grad()
        out = net(input_ids=ids, labels=ids)
        out.loss.backward()
        opt.step()

    step()
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        step()
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e3, batch


# --------------------------------------------------------------------------- #
# config 2: ResNet-50 CIFAR-10 DP step (TFJob-MultiWorkerMirrored analog)
# --------------------------------------------------------------------------- #

RESNET_BATCH = 256


def bench_resnet() -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from kubeflow_tpu.core.mesh import MeshSpec
    from kubeflow_tpu.data.synthetic import (
        ClassPrototypeDataset,
        local_shard_iterator,
    )
    from kubeflow_tpu.models.resnet import (
        ResNet,
        make_init_fn,
        make_loss_fn,
        resnet50_cifar,
    )
    from kubeflow_tpu.train.loop import TrainConfig, Trainer

    warmup = 5
    on_tpu = jax.default_backend() == "tpu"
    batch = RESNET_BATCH if on_tpu else 32
    model = ResNet(resnet50_cifar(dtype=jnp.bfloat16 if on_tpu else jnp.float32))
    trainer = Trainer(
        init_params=make_init_fn(model),
        loss_fn=make_loss_fn(model),
        optimizer=optax.sgd(0.1, momentum=0.9),
        config=TrainConfig(
            mesh=MeshSpec.data_parallel(jax.device_count()),
            global_batch=batch,
            steps=1000,
            log_every=10_000,
        ),
    )
    state = trainer.init_state()
    step_fn = trainer._build_step(state)
    ds = ClassPrototypeDataset(image_shape=(32, 32, 3))
    data = local_shard_iterator(ds, batch)
    batches = [trainer.global_batch_array(next(data)) for _ in range(4)]

    import numpy as np

    for i in range(warmup):
        state, m = step_fn(state, batches[i % len(batches)])
    np.asarray(jax.tree_util.tree_leaves(m)[0])
    state, times = _chained_step_times(
        step_fn, state, batches, reps=5, n_small=5, n_large=20
    )
    med, iqr = _median_iqr(times)
    img_per_s = batch / (med / 1e3)

    t_ms, t_batch = _torch_resnet_ms()
    t_img_per_s = t_batch / (t_ms / 1e3)
    return {
        "metric": "resnet50_train_throughput",
        "value": round(img_per_s, 1),
        "unit": "images/s",
        "vs_baseline": round(img_per_s / t_img_per_s, 3),
        "detail": {
            "step_time_ms": round(med, 3),
            "iqr_ms": round(iqr, 3),
            "global_batch": batch,
            "steady_state_drift": round(_steady_state_drift(times), 4),
            "reference_torch_cpu_images_per_s": round(t_img_per_s, 1),
            "reference_torch_batch": t_batch,
        },
    }


def _torch_resnet_ms() -> tuple[float, int]:
    """Reference side: torch ResNet-50 (bottleneck [3,4,6,3], CIFAR stem)
    train step on CPU — same architecture family as models/resnet.py."""
    import torch
    import torch.nn as nn
    import torch.nn.functional as F

    torch.manual_seed(0)

    class Bottleneck(nn.Module):
        def __init__(self, cin, filters, stride=1):
            super().__init__()
            cout = 4 * filters
            self.c1 = nn.Conv2d(cin, filters, 1, bias=False)
            self.n1 = nn.GroupNorm(32, filters)
            self.c2 = nn.Conv2d(filters, filters, 3, stride, 1, bias=False)
            self.n2 = nn.GroupNorm(32, filters)
            self.c3 = nn.Conv2d(filters, cout, 1, bias=False)
            self.n3 = nn.GroupNorm(32, cout)
            self.proj = (
                nn.Sequential(
                    nn.Conv2d(cin, cout, 1, stride, bias=False),
                    nn.GroupNorm(32, cout),
                )
                if (cin != cout or stride != 1)
                else None
            )

        def forward(self, x):
            r = x if self.proj is None else self.proj(x)
            y = F.relu(self.n1(self.c1(x)))
            y = F.relu(self.n2(self.c2(y)))
            return F.relu(self.n3(self.c3(y)) + r)

    class ResNet50(nn.Module):
        def __init__(self):
            super().__init__()
            self.stem = nn.Conv2d(3, 64, 3, 1, 1, bias=False)
            self.norm = nn.GroupNorm(32, 64)
            layers, cin = [], 64
            for stage, (n, f) in enumerate(
                zip((3, 4, 6, 3), (64, 128, 256, 512))
            ):
                for b in range(n):
                    stride = 2 if (stage > 0 and b == 0) else 1
                    layers.append(Bottleneck(cin, f, stride))
                    cin = 4 * f
            self.blocks = nn.Sequential(*layers)
            self.head = nn.Linear(2048, 10)

        def forward(self, x):
            x = F.relu(self.norm(self.stem(x)))
            x = self.blocks(x)
            return self.head(x.mean(dim=(2, 3)))

    from kubeflow_tpu.data.synthetic import ClassPrototypeDataset

    import numpy as np

    batch = 32
    ds = ClassPrototypeDataset(image_shape=(32, 32, 3))
    net = ResNet50()
    opt = torch.optim.SGD(net.parameters(), lr=0.1, momentum=0.9)

    def step(i):
        x, y = ds.batch(batch, step=i)
        xt = torch.from_numpy(np.transpose(x, (0, 3, 1, 2)))
        yt = torch.from_numpy(y.astype(np.int64))
        opt.zero_grad()
        F.cross_entropy(net(xt), yt).backward()
        opt.step()

    step(0)
    times = []
    for i in range(3):
        t0 = time.perf_counter()
        step(i + 1)
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e3, batch


# --------------------------------------------------------------------------- #
# config 4: Katib 16 parallel gang-scheduled trials — time-to-goal
# --------------------------------------------------------------------------- #


def bench_katib() -> dict:
    """16 parallel JAXJob trials contending for a simulated 4-slice fleet;
    bayesian (GP-EI) vs random search, same fleet, same goal."""
    from kubeflow_tpu.orchestrator.cluster import LocalCluster
    from kubeflow_tpu.orchestrator.envwire import WiringConfig
    from kubeflow_tpu.orchestrator.resources import Fleet
    from kubeflow_tpu.tune.controller import ExperimentController, JobTrialRunner
    from kubeflow_tpu.tune.spec import (
        AlgorithmSpec,
        ExperimentSpec,
        Objective,
        ObjectiveType,
        ParameterSpec,
        ParameterType,
    )

    # trial payload: no jax import (fast spawn); quadratic bowl in log10(lr),
    # optimum lr=1e-2 → loss 0; goal 1e-4 needs log-distance < 0.01 —
    # ~0.5% of the uniform-log space per draw, so random search needs ~200
    # draws in expectation (> max_trial_count) while GP-EI concentrates fast
    template = {
        "replicas": {
            "worker": {
                "replicas": 1,
                "command": [
                    sys.executable,
                    "-c",
                    "import math; lr=float('${trialParameters.lr}'); "
                    "print(f'step=1 loss={(math.log10(lr)+2.0)**2:.6f}')",
                ],
                "tpu": {"chips": 4},
            }
        },
        "run_policy": {"backoff_limit": 0},
    }

    goal = 1e-4

    def run(algorithm: str, seed: int, max_trials: int) -> dict:
        spec = ExperimentSpec(
            name=f"lr-sweep-{algorithm}-{seed}",
            parameters=(
                ParameterSpec(
                    "lr", ParameterType.DOUBLE, min=1e-4, max=1.0, log_scale=True
                ),
            ),
            objective=Objective("loss", ObjectiveType.MINIMIZE, goal=goal),
            algorithm=AlgorithmSpec(algorithm),
            parallel_trial_count=16,
            max_trial_count=max_trials,
            trial_template=template,
        )
        with LocalCluster(
            fleet=Fleet.homogeneous(4, "2x2"),
            wiring=WiringConfig(platform="cpu_sim", devices_per_worker=4),
            resync_period=0.05,
        ) as cluster:
            runner = JobTrialRunner(cluster, poll_s=0.05, timeout_s=120)
            t0 = time.perf_counter()
            status = ExperimentController(spec, runner, seed=seed).run()
            dt = time.perf_counter() - t0
        vals = [
            t.metrics["__objective__"]
            for t in status.trials
            if t.metrics.get("__objective__") is not None
        ]
        best = min(vals) if vals else None
        return {
            "seconds": dt,
            "launched": len(status.trials),
            "goal_met": best is not None and best <= goal,
            "best": best,
        }

    # random gets a larger budget so its time-to-goal is a real measurement,
    # not an early give-up at the bayesian budget
    bayes = run("bayesian", seed=1, max_trials=64)
    rand = run("random", seed=1, max_trials=512)
    both_met = bayes["goal_met"] and rand["goal_met"]
    return {
        # trials-to-goal IS the headline: each trial is minutes of chip
        # time on a real fleet, while the wall seconds here are dominated
        # by subprocess spawn on whatever host the driver runs (VERDICT
        # r04 weak-item 7: the wall number varied 6x between identical
        # runs on different hosts; the trial count did not)
        "metric": "katib_trials_to_goal",
        # the name asserts the goal was REACHED — an exhausted budget
        # must read as null, not as the budget number
        "value": bayes["launched"] if bayes["goal_met"] else None,
        "unit": "trials",
        "vs_baseline": (
            round(rand["launched"] / bayes["launched"], 3) if both_met else None
        ),
        "detail": {
            "algorithm": "bayesian (GP-EI)",
            "parallel_trials": 16,
            "fleet": "4 x 2x2 v5e slices (simulated)",
            "goal": goal,
            "bayes": {k: (round(v, 4) if isinstance(v, float) else v)
                      for k, v in bayes.items()},
            "random": {k: (round(v, 4) if isinstance(v, float) else v)
                       for k, v in rand.items()},
            "baseline_is": (
                "random search, same fleet/goal; vs_baseline = "
                "random trials-to-goal / bayesian trials-to-goal"
            ),
        },
    }


# --------------------------------------------------------------------------- #
# config 4b: serving goodput under open-loop load — steady, chaos-wedged,
# and autoscale-cycling runs through the REAL gateway + fleet (CPU anchor)
# --------------------------------------------------------------------------- #


def bench_serving_load() -> dict:
    """Seeded open-loop Poisson load against the real InferenceGateway +
    autoscaled ReplicaFleet over HTTP/SSE (kubeflow_tpu/loadgen). Three
    runs: steady (pinned fleet), chaos (same schedule + a WedgeEngine
    overlay mid-run), and scale (bursty on-off arrivals, min_replicas=0,
    cold-recovery timing). Deliberately NOT a device bench: its tiny
    CPU model exercises the harness and the control plane, so it lives
    in all_benches only and its times are never device numbers."""
    import asyncio
    import dataclasses as _dc

    from kubeflow_tpu.chaos.plan import FaultPlan, WedgeEngine
    from kubeflow_tpu.loadgen import ChaosOverlay, TenantSpec, WorkloadMix
    from kubeflow_tpu.loadgen.harness import HarnessConfig, run_serving_load

    # 4 distinct (prompt_len, budget) shapes keeps per-replica warmup
    # compiles bounded; deadline stays generous (CPU decode can't make a
    # tight one) while slo_ms=2000 is what goodput is scored against
    mix = WorkloadMix(
        prompt_lens=(6, 10),
        output_lens=(4, 8),
        tenants=(
            TenantSpec(
                "interactive", weight=2.0, priority=2,
                deadline_ms=30_000.0, slo_ms=2_000.0,
            ),
            TenantSpec(
                "batch", weight=1.0, priority=0, adapter="batch-v1",
                slo_ms=2_000.0,
            ),
        ),
        vocab=80,
        seed=7,
    )
    steady_cfg = HarnessConfig(
        seed=7, process="poisson", rate_rps=4.0, duration_s=8.0, mix=mix,
        initial_replicas=2, max_replicas=2, min_replicas=2,
    )
    chaos_cfg = _dc.replace(steady_cfg, chaos=ChaosOverlay(
        plan=FaultPlan(
            faults=(WedgeEngine(model="m", hold_s=3.0),), seed=7
        ),
        at_s=3.0, window_s=5.0,
    ))
    # warm requests finish in ~15ms, so average concurrency at the burst
    # rate is ~30*0.015 ≈ 0.45 — the target must sit below that for the
    # burst to drive a panic scale-up
    scale_cfg = HarnessConfig(
        seed=7, process="onoff", rate_rps=1.0, burst_rps=30.0,
        period_s=4.0, duration_s=8.0, mix=mix,
        initial_replicas=1, max_replicas=2, min_replicas=0,
        kpa_target=0.3, measure_cold_recovery=True,
    )

    steady = asyncio.run(run_serving_load(steady_cfg))
    chaos = asyncio.run(run_serving_load(chaos_cfg))
    scale = asyncio.run(run_serving_load(scale_cfg))

    g = steady["goodput"]["overall"]
    lat = steady["latency"]
    return {
        "metric": "serving_load_goodput",
        "value": g["goodput"],
        "unit": "fraction of offered load completed in SLO",
        "vs_baseline": None,
        "detail": {
            "steady": {
                "offered": g["offered"],
                "goodput": g["goodput"],
                "shed": g["shed"],
                "error": g["error"],
                "ttft_p50_ms": lat["ttft_ms"]["p50"],
                "ttft_p99_ms": lat["ttft_ms"]["p99"],
                "tpot_p50_ms": lat["tpot_ms"]["p50"],
                "client_e2e_p99_ms": lat["client_e2e_ms"]["p99"],
            },
            "chaos": {
                **{
                    k: chaos["chaos"][k]
                    for k in (
                        "faults", "window_s", "goodput_dip",
                        "client_visible_failures",
                    )
                },
                "goodput_in_window": chaos["chaos"]["in_window"]["goodput"],
                "goodput_outside_window": (
                    chaos["chaos"]["outside_window"]["goodput"]
                ),
            },
            "autoscale": {
                "scale_up_latency_s": (
                    scale.get("autoscale", {}).get("scale_up_latency_s")
                ),
                "replicas_peak": (
                    scale.get("autoscale", {}).get("replicas_peak")
                ),
                "cold_recovery_s": (
                    scale.get("cold_recovery", {}).get("recovery_s")
                ),
                "cold_recovery_outcome": (
                    scale.get("cold_recovery", {}).get("outcome")
                ),
            },
            "seeded": "same seed -> identical arrival schedule and "
            "workload plan across runs (arrivals are pure values)",
        },
    }


# --------------------------------------------------------------------------- #
# config 5: KServe BERT predictor p50/p99 + cold start (REST + gRPC)
# --------------------------------------------------------------------------- #


def bench_serving() -> dict:
    import numpy as np

    from kubeflow_tpu.serve.grpc_server import (
        GrpcInferenceClient,
        GrpcInferenceServer,
    )
    from kubeflow_tpu.serve.model import BucketSpec
    from kubeflow_tpu.serve.runtimes import BertRuntimeModel
    from kubeflow_tpu.serve.server import ModelServer

    # cold start = weights→HBM + compile of every serving bucket
    t0 = time.perf_counter()
    model = BertRuntimeModel(
        "bert", None, buckets=BucketSpec(batch_sizes=(1, 8), seq_lens=(128,))
    )
    model.load()
    model.warmup()
    cold_s = time.perf_counter() - t0

    server = ModelServer([model])
    text = "the quick brown fox [MASK] over the lazy dog in the bright morning"
    n_req = 40

    async def rest_latencies() -> list[float]:
        from aiohttp.test_utils import TestClient, TestServer

        lat = []
        async with TestClient(TestServer(server.build_app())) as client:
            for _ in range(3):  # connection + route warmup
                await client.post(
                    "/v1/models/bert:predict", json={"instances": [text]}
                )
            for _ in range(n_req):
                t = time.perf_counter()
                r = await client.post(
                    "/v1/models/bert:predict", json={"instances": [text]}
                )
                assert r.status == 200
                await r.json()
                lat.append(time.perf_counter() - t)
        return lat

    rest_lat = sorted(asyncio.run(rest_latencies()))

    g = GrpcInferenceServer(server.dataplane, port=0)
    port = g.start()
    try:
        c = GrpcInferenceClient(f"localhost:{port}")
        ids = np.asarray([model.tokenizer.encode(text)], np.int32)
        grpc_lat = []
        for _ in range(3):
            c.infer("bert", {"input_ids": ids})
        for _ in range(n_req):
            t = time.perf_counter()
            c.infer("bert", {"input_ids": ids})
            grpc_lat.append(time.perf_counter() - t)
        c.close()
    finally:
        g.stop()
    grpc_lat.sort()

    def pct(lat, q):
        return lat[min(len(lat) - 1, int(len(lat) * q))] * 1e3

    torch_p50 = _torch_bert_infer_p50()
    p50 = statistics.median(rest_lat) * 1e3
    return {
        "metric": "kserve_bert_p50_latency",
        "value": round(p50, 3),
        "unit": "ms",
        "vs_baseline": round(torch_p50 / p50, 3),
        "detail": {
            "rest_p99_ms": round(pct(rest_lat, 0.99), 3),
            "grpc_p50_ms": round(statistics.median(grpc_lat) * 1e3, 3),
            "grpc_p99_ms": round(pct(grpc_lat, 0.99), 3),
            "cold_start_s": round(cold_s, 2),
            "requests": n_req,
            "reference_torch_cpu_p50_ms": round(torch_p50, 2),
            "transport": "real aiohttp server + real gRPC server, batch-1",
        },
    }


def _torch_bert_infer_p50() -> float:
    """Reference side: HF torch BERT-base forward, CPU, batch-1 seq-128."""
    import torch
    from transformers import BertConfig as HFConfig
    from transformers import BertForMaskedLM as HFBert

    net = HFBert(HFConfig()).eval()
    ids = torch.randint(0, 30522, (1, 128))
    with torch.no_grad():
        net(input_ids=ids)
        lat = []
        for _ in range(10):
            t0 = time.perf_counter()
            net(input_ids=ids)
            lat.append(time.perf_counter() - t0)
    return statistics.median(lat) * 1e3


# --------------------------------------------------------------------------- #
# config 6 (beyond BASELINE): generative LM decode throughput — the
# huggingfaceserver/vLLM analog (SURVEY.md §2.2), whole-generation-on-device
# --------------------------------------------------------------------------- #


def bench_generate() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
    from kubeflow_tpu.serve.generate import make_generate_fn

    on_tpu = jax.default_backend() == "tpu"
    cfg = TransformerConfig(
        vocab_size=32768,
        d_model=1024,
        n_layers=12,
        n_heads=16,
        d_ff=4096,
        attn_impl="flash" if on_tpu else "reference",
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    batch, prompt_len, max_new = 8, 128, 64
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    params = jax.device_put(params)
    prompt = np.ones((batch, prompt_len), np.int32)
    plen = np.full((batch,), prompt_len, np.int32)
    temps = np.zeros((batch,), np.float32)

    def timed(gen, seed):
        t0 = time.perf_counter()
        _, n_valid = gen(params, prompt, plen, jax.random.PRNGKey(seed), temps)
        np.asarray(n_valid)  # host transfer = real sync
        return time.perf_counter() - t0

    # two generation lengths: the difference isolates pure decode steps
    # (prefill and the constant sync cost both cancel)
    short_new = 16
    gen_long = jax.jit(
        make_generate_fn(model, cfg, max_new_tokens=max_new, eos_id=1)
    )
    gen_short = jax.jit(
        make_generate_fn(model, cfg, max_new_tokens=short_new, eos_id=1)
    )
    timed(gen_long, 0)
    timed(gen_short, 0)  # compiles
    t_long = min(timed(gen_long, s) for s in (1, 2))
    t_short = min(timed(gen_short, s) for s in (1, 2))
    step_s = (t_long - t_short) / (max_new - short_new)
    prefill_s = max(t_short - short_new * step_s, 0.0)
    tok_per_s = batch * max_new / t_long  # aggregate: prefill amortized

    torch_tps = _torch_generate_tps(batch=batch)
    return {
        "metric": "lm_decode_throughput",
        "value": round(tok_per_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tok_per_s / torch_tps, 3),
        "detail": {
            "ms_per_decode_step": round(step_s * 1e3, 3),
            "prefill_ms": round(prefill_s * 1e3, 2),
            "batch": batch,
            "prompt_len": prompt_len,
            "max_new_tokens": max_new,
            "model": "1024d x 12L (~200M params)",
            "dtype": "bfloat16" if on_tpu else "float32",
            "design": "prefill + lax.scan decode, one device program",
            "reference_torch_cpu_tokens_per_s": round(torch_tps, 1),
            "baseline_is": (
                "torch GPT-2-class greedy generate, SAME batch, CPU; "
                "both sides aggregate tokens/s with prefill amortized"
            ),
        },
    }


def _torch_generate_tps(batch: int = 8) -> float:
    """Reference side: HF torch GPT-2-class greedy generation on CPU at the
    SAME batch size (decode throughput scales ~linearly with batch; a
    batch-1 reference would inflate vs_baseline by ~batch x)."""
    import torch
    from transformers import GPT2Config, GPT2LMHeadModel

    torch.manual_seed(0)
    net = GPT2LMHeadModel(
        GPT2Config(n_embd=1024, n_layer=12, n_head=16, vocab_size=32768)
    ).eval()
    ids = torch.ones((batch, 128), dtype=torch.long)
    new = 32
    with torch.no_grad():
        net.generate(ids, max_new_tokens=2, do_sample=False)  # warm caches
        t0 = time.perf_counter()
        net.generate(ids, max_new_tokens=new, do_sample=False)
        dt = time.perf_counter() - t0
    return batch * new / dt


# --------------------------------------------------------------------------- #
# config 7 (beyond BASELINE): continuous-batching serving throughput — the
# vLLM-scheduler analog (serve/engine.py). 16 mixed-length requests arrive
# CONCURRENTLY; the engine shares one decode batch. Baseline = the same 16
# served one-at-a-time through the whole-batch generate path (what a server
# without continuous batching does under concurrent load).
# --------------------------------------------------------------------------- #


def bench_engine() -> dict:
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
    from kubeflow_tpu.serve.engine import LMEngine
    from kubeflow_tpu.serve.generate import make_generate_fn

    on_tpu = jax.default_backend() == "tpu"
    cfg = TransformerConfig(
        vocab_size=32768,
        d_model=1024 if on_tpu else 128,
        n_layers=12 if on_tpu else 2,
        n_heads=16 if on_tpu else 4,
        d_ff=4096 if on_tpu else 256,
        causal=True,
        attn_impl="flash" if on_tpu else "reference",
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    max_new = 48
    rng = np.random.default_rng(0)
    # mixed prompt lengths; every request gets the SAME token budget so the
    # sequential baseline does identical work (its generate program always
    # runs max_new steps — per-request budgets would unfairly pad its time)
    requests = [
        [int(t) for t in rng.integers(2, cfg.vocab_size, size=int(n))]
        for n in rng.integers(16, 120, size=16)
    ]
    budgets = [max_new] * 16

    def run_fanout(e) -> tuple[float, int, dict[int, list[int]]]:
        """The 16-way concurrent workload, timed: wall seconds, total
        tokens, per-request outputs. Shared by the dense and paged phases
        so both measure the identical protocol."""
        outs: dict[int, list[int]] = {}

        def worker(i):
            outs[i] = e.submit(requests[i], max_new_tokens=budgets[i])

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(16)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        return (
            time.perf_counter() - t0,
            sum(len(v) for v in outs.values()),
            outs,
        )

    eng = LMEngine(
        model, cfg, params, max_batch=8, max_seq=192, chunk_steps=8,
        prefill_buckets=(128,), eos_id=1,
    ).start()
    try:
        for _ in range(2):  # compile prefill + chunk
            eng.submit(requests[0][:16], max_new_tokens=8)
        t_engine, engine_tokens, _ = run_fanout(eng)
    finally:
        eng.stop()

    # baseline: same requests, one at a time, whole-batch generate path
    gen = jax.jit(make_generate_fn(model, cfg, max_new_tokens=max_new, eos_id=1))
    prompt0 = np.zeros((1, 128), np.int32)
    prompt0[0, : len(requests[0])] = requests[0]
    _ = gen(params, prompt0, np.asarray([len(requests[0])], np.int32),
            jax.random.PRNGKey(0), np.zeros((1,), np.float32))  # compile
    seq_tokens = 0
    t0 = time.perf_counter()
    for i, ids in enumerate(requests):
        prompt = np.zeros((1, 128), np.int32)
        prompt[0, : len(ids)] = ids
        toks, n_valid = gen(
            params, prompt, np.asarray([len(ids)], np.int32),
            jax.random.PRNGKey(i), np.zeros((1,), np.float32),
        )
        seq_tokens += min(int(np.asarray(n_valid)[0]), budgets[i])
    t_seq = time.perf_counter() - t0

    tok_per_s = engine_tokens / t_engine
    seq_tok_per_s = seq_tokens / t_seq if t_seq > 0 else float("nan")

    # phase 2: shared-system-prompt workload — automatic prefix caching
    # should collapse the repeated 112-token prefill to a 16-token suffix
    shared = [int(t) for t in rng.integers(2, cfg.vocab_size, size=112)]
    tails = [
        [int(t) for t in rng.integers(2, cfg.vocab_size, size=8)]
        for _ in range(8)
    ]

    def run_shared(entries: int) -> float:
        e = LMEngine(
            model, cfg, params, max_batch=1, max_seq=192, chunk_steps=8,
            prefill_buckets=(128,), eos_id=1, prefix_cache_entries=entries,
        ).start()
        try:
            e.submit(shared + tails[0][:4], max_new_tokens=4)  # compile+seed
            # warm the HIT path too (implant + suffix-prefill programs) so
            # the timed loop measures the steady state, not XLA compiles
            e.submit(shared + [9] * 8, max_new_tokens=4)
            t0 = time.perf_counter()
            for tail in tails:
                e.submit(shared + tail, max_new_tokens=4)
            return time.perf_counter() - t0
        finally:
            e.stop()

    t_nocache = run_shared(0)
    t_cache = run_shared(8)

    # phase 3: paged-KV HBM density (serve/paging.py, the vLLM block-table
    # analog). An engine provisioned for 512-token context serves the same
    # 16 concurrent mixed-length requests out of a 2624-token page pool —
    # the dense layout bills 16 x 512 = 8192 cache tokens for the identical
    # workload. All 16 rows must be RESIDENT AT ONCE for the density claim.
    paged_max_seq, pool_tokens = 512, 64 * 41  # 40 usable pages + scratch
    pe = LMEngine(
        model, cfg, params, max_batch=16, max_seq=paged_max_seq,
        chunk_steps=8, prefill_buckets=(128,), eos_id=1,
        kv_pool_tokens=pool_tokens, page_size=64,
    ).start()
    try:
        # warm BOTH ends: the longest request at full budget walks the
        # large pages_w chunk widths, and a short low-budget one compiles
        # the pages_w=1 program (reachable late in the run when only short
        # rows remain active) — so no compile lands in the timed window
        longest = max(range(16), key=lambda i: len(requests[i]))
        pe.submit(requests[longest], max_new_tokens=max_new)
        pe.submit(requests[0][:16], max_new_tokens=8)
        t_paged, paged_tokens, _ = run_fanout(pe)
        paged_concurrent = pe.stats["max_concurrent"]
        pages_peak = pe.stats.get("kv_pages_used_peak", 0)
    finally:
        pe.stop()
    paged_tok_per_s = paged_tokens / t_paged if t_paged > 0 else float("nan")
    dense_rectangle = 16 * paged_max_seq

    return {
        "metric": "engine_concurrent_throughput",
        "value": round(tok_per_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tok_per_s / seq_tok_per_s, 3),
        "detail": {
            "requests": 16,
            "max_batch": 8,
            "chunk_steps": 8,
            "engine_tokens": engine_tokens,
            "engine_seconds": round(t_engine, 3),
            "sequential_tokens_per_s": round(seq_tok_per_s, 1),
            "prefix_cache_speedup": (
                round(t_nocache / t_cache, 3) if t_cache > 0 else None
            ),
            "shared_prefix_s_nocache": round(t_nocache, 3),
            "shared_prefix_s_cached": round(t_cache, 3),
            "shared_prefix_workload": (
                "8 x (112-token shared prefix + 8-token tail), 4 new "
                "tokens each, batch-1 engine"
            ),
            "model": ("1024d x 12L" if on_tpu else "tiny-cpu"),
            "baseline_is": (
                "same 16 mixed-length requests served one-at-a-time "
                "through the whole-batch generate path (a server without "
                "continuous batching under concurrent load)"
            ),
            "paged_kv": {
                "hbm_density_x": round(dense_rectangle / pool_tokens, 2),
                "dense_cache_tokens": dense_rectangle,
                "pool_tokens": pool_tokens,
                "kv_pages_used_peak": pages_peak,
                "page_size": 64,
                "max_concurrent": paged_concurrent,
                "all_resident": paged_concurrent == 16,
                "tokens_per_s": round(paged_tok_per_s, 1),
                "workload": (
                    "same 16 concurrent requests, engine provisioned for "
                    "512-token context: dense bills 16x512 cache tokens, "
                    "the page pool holds 2624"
                ),
            },
        },
    }


# --------------------------------------------------------------------------- #
# config 7b (beyond BASELINE): pipelined-decode microbench — device-resident
# carry + one-chunk-ahead dispatch (serve/engine.py pipeline_depth=1) vs the
# inline per-chunk-H2D/D2H loop (pipeline_depth=0), dense AND paged. Runs on
# the CPU backend too: the host-overhead gap the pipeline removes exists on
# any backend, just with different magnitudes.
# --------------------------------------------------------------------------- #


def bench_engine_decode() -> dict:
    """tokens/s + decode-gap for ``pipeline_depth`` 0/1, dense and paged.

    The workload is pure decode steady state (short prompts, long budgets,
    all rows admitted up front), so the measured delta is exactly what the
    tentpole targets: per-chunk D2H sync + per-row H2D + host postprocess
    dead time between device chunks.
    """
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
    from kubeflow_tpu.serve.engine import LMEngine

    on_tpu = jax.default_backend() == "tpu"
    cfg = TransformerConfig(
        vocab_size=32768,
        d_model=1024 if on_tpu else 128,
        n_layers=12 if on_tpu else 2,
        n_heads=16 if on_tpu else 4,
        d_ff=4096 if on_tpu else 256,
        causal=True,
        attn_impl="flash" if on_tpu else "reference",
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    n_req, max_new = 8, 64
    rng = np.random.default_rng(0)
    requests = [
        [int(t) for t in rng.integers(2, cfg.vocab_size, size=int(n))]
        for n in rng.integers(8, 28, size=n_req)
    ]

    def run(depth: int, paged: bool) -> dict:
        kw: dict = dict(
            max_batch=n_req, max_seq=128, chunk_steps=8,
            prefill_buckets=(32,), eos_id=1, pipeline_depth=depth,
        )
        if paged:
            kw.update(kv_pool_tokens=128 * (n_req + 1), page_size=32)
        eng = LMEngine(model, cfg, params, **kw).start()
        try:
            eng.submit(requests[0][:8], max_new_tokens=max_new)  # compile
            outs: dict[int, list[int]] = {}

            def worker(i):
                outs[i] = eng.submit(requests[i], max_new_tokens=max_new)

            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=worker, args=(i,))
                for i in range(n_req)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(600)
            dt = time.perf_counter() - t0
            tokens = sum(len(v) for v in outs.values())
            return {
                "tokens_per_s": round(tokens / dt, 1),
                "tokens": tokens,
                "seconds": round(dt, 3),
                "chunks": eng.stats["chunks"],
                "carry_uploads": eng.overlap["carry_uploads"],
                "decode_gap_ms": round(eng.overlap["decode_gap_ms"], 3),
                "d2h_drain_ms": round(eng.overlap["d2h_drain_ms"], 3),
                "slot_occupancy": round(eng.overlap["slot_occupancy"], 3),
            }
        finally:
            eng.stop()

    dense = {d: run(d, paged=False) for d in (0, 1)}
    paged = {d: run(d, paged=True) for d in (0, 1)}
    speed = (
        dense[1]["tokens_per_s"] / dense[0]["tokens_per_s"]
        if dense[0]["tokens_per_s"]
        else None
    )
    return {
        "metric": "engine_decode_pipelined_tokens_per_s",
        "value": dense[1]["tokens_per_s"],
        "unit": "tokens/s",
        "vs_baseline": round(speed, 3) if speed else None,
        "detail": {
            "requests": n_req,
            "max_new_tokens": max_new,
            "chunk_steps": 8,
            "model": ("1024d x 12L" if on_tpu else "tiny-cpu"),
            "dense_inline_depth0": dense[0],
            "dense_pipelined_depth1": dense[1],
            "paged_inline_depth0": paged[0],
            "paged_pipelined_depth1": paged[1],
            "paged_speedup": (
                round(paged[1]["tokens_per_s"] / paged[0]["tokens_per_s"], 3)
                if paged[0]["tokens_per_s"]
                else None
            ),
            "baseline_is": (
                "identical engine + workload at pipeline_depth=0: per-chunk "
                "H2D of every per-row array, blocking D2H before the next "
                "dispatch, host postprocess as dead bus time"
            ),
            "speculative": _bench_spec_decode(),
            "paged_attention": _bench_paged_attention(),
        },
    }


def _bench_paged_attention() -> dict:
    """The paged read path the engine chooses (the Pallas kernel for the
    decode step: interpret mode on CPU — its tokens/s are a CORRECTNESS
    trajectory, not a speed claim) × fp32/fp16 KV vs int8 KV. Reports
    tokens/s, pool bytes per resident token (the density number the
    paged cache exists for — int8 pools are exactly half the
    bf16 bill, a quarter of f32, with the f32 scale side arrays itemized
    separately), max concurrent residents, and the int8 greedy
    token-match rate vs the unquantized run.

    The model is random-init with the unembed tied to the embedding and
    the residual branches tempered: a fully random head yields near-iid
    logits whose top-1/top-2 margin is a fraction of the logit std, so
    any perturbation flips an argmax every ~30 steps and the greedy
    stream cascades — the match rate would measure chaos, not
    quantization fidelity. Trained LMs have sharp margins; the tied
    sharp-margin surrogate restores that property while keeping the
    attention path (and hence the int8 KV error) live in the graph."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import traverse_util

    from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
    from kubeflow_tpu.serve.engine import LMEngine

    on_tpu = jax.default_backend() == "tpu"
    cfg = TransformerConfig(
        vocab_size=32768,
        d_model=1024 if on_tpu else 128,
        n_layers=12 if on_tpu else 2,
        n_heads=16 if on_tpu else 4,
        d_ff=4096 if on_tpu else 256,
        causal=True,
        attn_impl="flash" if on_tpu else "reference",
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        interpret_kernels=not on_tpu,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    flat = traverse_util.flatten_dict(params)
    sharp = {}
    for k, v in flat.items():
        name = "/".join(k)
        if name == "unembed/kernel":
            v = flat[("embed", "embedding")].T
        elif "o_proj" in name:
            v = v * 0.5
        elif "down_proj" in name:
            v = v * 0.1
        sharp[k] = v
    params = traverse_util.unflatten_dict(sharp)
    n_req, max_new = 8, 48
    pool_tokens = 128 * (n_req + 1)
    rng = np.random.default_rng(0)
    requests = [
        [int(t) for t in rng.integers(2, cfg.vocab_size, size=int(n))]
        for n in rng.integers(8, 28, size=n_req)
    ]

    def run(quant: str) -> dict:
        eng = LMEngine(
            model, cfg, params,
            max_batch=n_req, max_seq=128, chunk_steps=8,
            prefill_buckets=(32,), eos_id=1, pipeline_depth=1,
            kv_pool_tokens=pool_tokens, page_size=32,
            kv_quant=quant,
        ).start()
        try:
            eng.submit(requests[0][:8], max_new_tokens=max_new)  # compile
            outs: dict[int, list[int]] = {}

            def worker(i):
                outs[i] = eng.submit(requests[i], max_new_tokens=max_new)

            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=worker, args=(i,))
                for i in range(n_req)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(600)
            dt = time.perf_counter() - t0
            tokens = sum(len(v) for v in outs.values())
            kv_bytes = sum(
                int(lc[w].nbytes)
                for lc in eng.cache.values() for w in ("k", "v")
            )
            scale_bytes = sum(
                int(arr.nbytes)
                for lc in eng.cache.values()
                for w, arr in lc.items() if w.endswith("_scale")
            )
            return {
                "outs": outs,
                "tokens_per_s": round(tokens / dt, 1),
                "pool_bytes_per_resident_token": round(
                    kv_bytes / pool_tokens, 1
                ),
                "scale_bytes_per_resident_token": round(
                    scale_bytes / pool_tokens, 1
                ),
                "max_concurrent_residents": eng.stats["max_concurrent"],
                "kv_pages_used_peak": eng.stats["kv_pages_used_peak"],
                "decode_chunks_kernel_read": (
                    eng.stats["decode_chunks_kernel_read"]
                ),
                "kv_quant_error": (
                    round(eng.overlap["kv_quant_error"], 5)
                    if quant == "int8" else None
                ),
            }
        finally:
            eng.stop()

    out: dict = {
        "pool_tokens": pool_tokens,
        "page_size": 32,
        "kernel_mode": "compiled" if on_tpu else "interpret",
        "note": (
            "kernel tokens/s on CPU runs the Pallas interpreter — track "
            "byte-parity and density here, speed on the chip session"
        ),
    }
    # the engine chooses the read path itself (the Pallas kernel for the
    # decode step here: compiled on a TPU, interpreted on the CPU)
    base_outs = None
    for quant in ("none", "int8"):
        r = run(quant)
        outs = r.pop("outs")
        if quant == "none":
            base_outs = outs
            r["token_match_vs_fp"] = 1.0
        else:
            pairs = [
                (a, b)
                for i in outs
                for a, b in zip(base_outs[i], outs[i])
            ]
            r["token_match_vs_fp"] = round(
                float(np.mean([a == b for a, b in pairs])), 4
            )
        out[quant] = r
    halved = (
        out["int8"]["pool_bytes_per_resident_token"]
        <= out["none"]["pool_bytes_per_resident_token"] / 2 + 1e-9
    )
    out["int8_pool_bytes_halved_vs_fp16_equiv"] = halved
    return out


def _bench_spec_decode() -> dict:
    """Speculative-decode variants of the engine_decode workload: K=0 vs
    K=4 (``spec_draft_tokens``), repetitive/templated vs random prompts,
    dense + paged.

    The model is a tiny transformer whose attention/MLP write-back
    projections are zeroed, making its greedy output a deterministic
    token chain that cycles — a CPU-runnable stand-in for the induction
    behavior trained models exhibit on templated/RAG traffic (the
    workload prompt-lookup exists for; random weights never echo their
    history, so acceptance on them is honestly ~0, and that variant is
    reported as the contrast). ``chunk_steps=1`` is the latency-oriented
    configuration where per-forward fixed cost dominates — exactly the
    memory-bound-decode regime speculation targets on real chips.
    ``forwards_per_token`` (chunk counts) is the deterministic measure;
    tokens/s carries host-machine noise."""
    import threading

    import flax
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
    from kubeflow_tpu.serve.engine import LMEngine

    vocab, n_req, max_new = 64, 8, 96
    max_seq = 32 + max_new + 8  # bucket + budget + K headroom
    cfg = TransformerConfig(
        vocab_size=vocab, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        causal=True, attn_impl="reference", dtype=jnp.float32,
    )
    model = TransformerLM(cfg)
    raw_params = model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    flat = flax.traverse_util.flatten_dict(raw_params)
    copy_params = flax.traverse_util.unflatten_dict({
        k: (jnp.zeros_like(v) if k[-2] in ("o_proj", "down_proj") else v)
        for k, v in flat.items()
    })
    rng = np.random.default_rng(0)
    repetitive = [
        [int(t) for t in (list(rng.integers(2, vocab, size=4)) * 8)[:16]]
        for _ in range(n_req)
    ]
    random_prompts = [
        [int(t) for t in rng.integers(2, vocab, size=16)]
        for _ in range(n_req)
    ]

    def run(k: int, paged: bool, prompts, params) -> dict:
        kw: dict = dict(
            max_batch=n_req, max_seq=max_seq, chunk_steps=1,
            prefill_buckets=(32,), eos_id=vocab + 1, pipeline_depth=1,
            spec_draft_tokens=k,
        )
        if paged:
            kw.update(
                kv_pool_tokens=-(-max_seq // 32) * 32 * (n_req + 1),
                page_size=32,
            )
        eng = LMEngine(model, cfg, params, **kw).start()
        try:
            eng.submit(prompts[0][:8], max_new_tokens=max_new)  # compile
            chunks0 = eng.stats["chunks"]
            outs: dict[int, list[int]] = {}

            def worker(i):
                outs[i] = eng.submit(prompts[i], max_new_tokens=max_new)

            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=worker, args=(i,))
                for i in range(n_req)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(600)
            dt = time.perf_counter() - t0
            tokens = sum(len(v) for v in outs.values())
            forwards = eng.stats["chunks"] - chunks0
            return {
                "outs": outs,
                "tokens_per_s": round(tokens / dt, 1),
                "forwards": forwards,
                "forwards_per_token": round(forwards / max(tokens, 1), 3),
                "spec_proposed": eng.stats["spec_proposed"],
                "spec_accepted": eng.stats["spec_accepted"],
                "spec_acceptance": round(
                    eng.overlap["spec_acceptance"], 3
                ),
            }
        finally:
            eng.stop()

    out: dict = {
        "spec_draft_tokens": 4, "spec_ngram": 3, "chunk_steps": 1,
        "workloads": (
            "repetitive = templated prompts on the copy-deterministic "
            "model (the traffic speculation wins on); random = "
            "incompressible prompts on raw random weights (the honest "
            "near-zero-acceptance contrast)"
        ),
    }
    for mode, paged in (("dense", False), ("paged", True)):
        for workload, prompts, params in (
            ("repetitive", repetitive, copy_params),
            ("random", random_prompts, raw_params),
        ):
            base = run(0, paged, prompts, params)
            spec = run(4, paged, prompts, params)
            identical = base.pop("outs") == spec.pop("outs")
            out[f"{mode}_{workload}"] = {
                "k0": base,
                "k4": spec,
                "tokens_identical": identical,
                "speedup_tokens_per_s": (
                    round(spec["tokens_per_s"] / base["tokens_per_s"], 3)
                    if base["tokens_per_s"]
                    else None
                ),
                "speedup_forwards": (
                    round(base["forwards"] / spec["forwards"], 3)
                    if spec["forwards"]
                    else None
                ),
            }
    return out


# --------------------------------------------------------------------------- #
# config 8b (beyond BASELINE): disaggregated prefill/decode serving.
# Baseline = ONE colocated engine interleaving prefill chunks with decode
# chunks on its scheduler; disagg = a prefill engine that only prefills and
# a decode engine that only decodes, wired by the per-request KV-span ship
# (prefill_span → npz codec → prepare_kv_span → inject) — the in-process
# equivalent of the gateway's x-kft-prefill-peer path, minus the HTTP.
# --------------------------------------------------------------------------- #


def bench_engine_disagg() -> dict:
    """TTFT/TPOT p50/p99 for disagg vs colocated under concurrent load,
    plus KV-ship bytes and latency. CPU-runnable: on CPU the numbers are a
    TRAJECTORY for the interference effect (decode chunks delaying new
    requests' prefill and vice versa), not a throughput claim."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
    from kubeflow_tpu.serve.engine import LMEngine
    from kubeflow_tpu.serve.kv_codec import decode_kv_entries, encode_kv_entries

    on_tpu = jax.default_backend() == "tpu"
    cfg = TransformerConfig(
        vocab_size=32768,
        d_model=1024 if on_tpu else 128,
        n_layers=12 if on_tpu else 2,
        n_heads=16 if on_tpu else 4,
        d_ff=4096 if on_tpu else 256,
        causal=True,
        attn_impl="flash" if on_tpu else "reference",
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    rng = np.random.default_rng(0)
    # the DistServe workload shape: a batch of RESIDENT rows in decode
    # steady state, plus LONG-prompt/short-decode arrivals whose chunked
    # prefill must (colocated) interleave with the residents' chunks
    n_res, res_new = 4, 96
    n_inc, inc_new = 6, 16
    res_prompts = [
        [int(t) for t in rng.integers(2, cfg.vocab_size, size=16)]
        for _ in range(n_res)
    ]
    inc_prompts = [
        [int(t) for t in rng.integers(2, cfg.vocab_size, size=int(n))]
        for n in rng.integers(160, 225, size=n_inc)
    ]

    def mk() -> LMEngine:
        # eos_id=-1: no stream ends early, so every TPOT sample sees its
        # full budget of inter-token gaps. prefill_chunk=32 is the
        # interference knob: a 224-token prompt is 7 pieces, each of which
        # (colocated) waits out a 16-step decode chunk of the residents.
        return LMEngine(
            model, cfg, params, max_batch=n_res + n_inc, max_seq=256,
            chunk_steps=16, prefill_buckets=(32, 256), prefill_chunk=32,
            eos_id=-1, kv_pool_tokens=256 * 8, page_size=32,
        ).start()

    def pct(xs, q):
        xs = sorted(xs)
        return round(xs[min(len(xs) - 1, int(q * len(xs)))] * 1e3, 2)

    ship = {"bytes": 0, "ships": 0, "ms": []}
    lock = threading.Lock()

    def run(pre: LMEngine | None, dec: LMEngine) -> dict:
        """``pre is None`` → colocated (dec prefills everything itself);
        otherwise EVERY request's prefill runs on ``pre`` and ships —
        the decode engine must execute zero prefill pieces."""
        # warm both shape buckets before timing
        dec.submit(res_prompts[0][:8], max_new_tokens=2)
        (pre or dec).submit(inc_prompts[0], max_new_tokens=2)
        pieces0 = dec.stats["prefill_pieces"]
        res_tpot: dict[int, float] = {}
        inc_ttft: dict[int, float] = {}
        outs: dict[str, list[int]] = {}

        def start_stream(ids, max_new):
            if pre is None:
                return dec.stream(ids, max_new_tokens=max_new)
            t0 = time.perf_counter()
            tree, meta = pre.prefill_span(ids)
            blob = encode_kv_entries([(tuple(ids), tree)], meta)
            entries, m = decode_kv_entries(blob)
            span = dec.prepare_kv_span(ids, entries[0][1], m)
            with lock:
                ship["bytes"] += len(blob)
                ship["ships"] += 1
                ship["ms"].append((time.perf_counter() - t0) * 1e3)
            return dec.stream(ids, max_new_tokens=max_new, kv_span=span)

        def resident(i):
            # stream() yields per-chunk token lists; the first yield is
            # the admission token, so TPOT averages over everything after
            toks, first, nfirst, last = [], None, 0, None
            for chunk in start_stream(res_prompts[i], res_new):
                now = time.perf_counter()
                if first is None:
                    first, nfirst = now, len(chunk)
                last = now
                toks.extend(chunk)
            res_tpot[i] = (last - first) / max(1, len(toks) - nfirst)
            outs[f"res{i}"] = toks

        def incoming(i):
            # arrive once the residents are decoding
            time.sleep(0.3 + 0.05 * i)
            t0 = time.perf_counter()
            toks, first = [], None
            for chunk in start_stream(inc_prompts[i], inc_new):
                first = first or time.perf_counter()
                toks.extend(chunk)
            inc_ttft[i] = first - t0
            outs[f"inc{i}"] = toks

        threads = [
            threading.Thread(target=resident, args=(i,)) for i in range(n_res)
        ] + [
            threading.Thread(target=incoming, args=(i,)) for i in range(n_inc)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        return {
            "ttft_p50_ms": pct(inc_ttft.values(), 0.50),
            "ttft_p99_ms": pct(inc_ttft.values(), 0.99),
            "resident_tpot_p50_ms": pct(res_tpot.values(), 0.50),
            "resident_tpot_p99_ms": pct(res_tpot.values(), 0.99),
            "seconds": round(time.perf_counter() - t0, 3),
            "tokens": sum(len(v) for v in outs.values()),
            "decode_prefill_pieces": dec.stats["prefill_pieces"] - pieces0,
            "outs": outs,
        }

    # -- colocated: one engine interleaves prefill + decode chunks ------- #
    colo = mk()
    try:
        colocated = run(None, colo)
    finally:
        colo.stop()

    # -- disagg: prefill pool + decode pool + per-request KV ship -------- #
    pre, dec = mk(), mk()
    try:
        disagg = run(pre, dec)
    finally:
        pre.stop()
        dec.stop()
    decode_prefill_pieces = disagg["decode_prefill_pieces"]

    identical = colocated.pop("outs") == disagg.pop("outs")
    ship_ms = sorted(ship["ms"])
    return {
        "metric": "engine_disagg_ttft_p99_ms",
        "value": disagg["ttft_p99_ms"],
        "unit": "ms",
        "vs_baseline": (
            round(colocated["ttft_p99_ms"] / disagg["ttft_p99_ms"], 3)
            if disagg["ttft_p99_ms"]
            else None
        ),
        "detail": {
            "residents": {"n": n_res, "prompt_tokens": 16, "max_new": res_new},
            "incoming": {
                "n": n_inc,
                "prompt_tokens": [len(p) for p in inc_prompts],
                "max_new": inc_new,
            },
            "model": ("1024d x 12L" if on_tpu else "tiny-cpu"),
            "colocated": colocated,
            "disagg": disagg,
            "tokens_identical": identical,
            "decode_prefill_pieces": decode_prefill_pieces,
            "kv_ship": {
                "ships": ship["ships"],
                "total_bytes": ship["bytes"],
                "bytes_per_ship": (
                    ship["bytes"] // ship["ships"] if ship["ships"] else 0
                ),
                "p50_ms": (
                    round(ship_ms[len(ship_ms) // 2], 2) if ship_ms else None
                ),
                "p99_ms": (
                    round(ship_ms[min(len(ship_ms) - 1,
                                      int(0.99 * len(ship_ms)))], 2)
                    if ship_ms
                    else None
                ),
            },
            "baseline_is": (
                "one colocated engine whose scheduler interleaves prefill "
                "chunks with resident rows' decode chunks — the "
                "interference disaggregation removes by giving prefill its "
                "own pool and shipping the finished span"
            ),
        },
    }


# --------------------------------------------------------------------------- #
# config 7b (beyond BASELINE): mid-stream failover resume overhead — the
# engine-side cost of continuing a committed stream on a fresh replica
# (suffix-prefill of prompt+committed) vs starting the same stream cold.
# Baseline = the uninterrupted request's TTFT on the same engine.
# --------------------------------------------------------------------------- #


def bench_engine_resume() -> dict:
    """TTFR (time to first RESUMED token) of a mid-stream-failover
    admission vs the uninterrupted stream's TTFT, on one warm engine.

    The resumed admission prefills prompt+committed as one suffix and
    emits only tokens past the prefix — the gateway's failover path pays
    exactly this on the surviving replica, so TTFR/TTFT is the client's
    observed mid-stream hiccup relative to a cold start. Also asserts the
    spliced token stream equals the uninterrupted one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
    from kubeflow_tpu.serve.engine import LMEngine

    on_tpu = jax.default_backend() == "tpu"
    cfg = TransformerConfig(
        vocab_size=32768,
        d_model=1024 if on_tpu else 128,
        n_layers=12 if on_tpu else 2,
        n_heads=16 if on_tpu else 4,
        d_ff=4096 if on_tpu else 256,
        causal=True,
        attn_impl="flash" if on_tpu else "reference",
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    rng = np.random.default_rng(0)
    n_req, prompt_len, max_new = 8, 48, 32
    prompts = [
        [int(t) for t in rng.integers(2, cfg.vocab_size, size=prompt_len)]
        for _ in range(n_req)
    ]
    eng = LMEngine(
        model, cfg, params, max_batch=4, max_seq=256, chunk_steps=8,
        prefill_buckets=(64, 128), eos_id=-1,
    ).start()

    def first_token_latency(ids, resume_tokens=None):
        toks = []
        t0 = time.perf_counter()
        ttfr = None
        for chunk in eng.stream(
            ids, max_new_tokens=max_new, resume_tokens=resume_tokens
        ):
            if ttfr is None:
                ttfr = time.perf_counter() - t0
            toks.extend(chunk)
        return ttfr, toks

    def pct(xs, q):
        xs = sorted(xs)
        return round(xs[min(len(xs) - 1, int(q * len(xs)))] * 1e3, 2)

    try:
        # warm both prefill buckets through their compiles
        first_token_latency(prompts[0])
        first_token_latency(prompts[0], resume_tokens=[5] * (max_new // 2))
        ttft, ttfr = [], []
        identical = True
        for ids in prompts:
            t_cold, full = first_token_latency(ids)
            ttft.append(t_cold)
            cut = len(full) // 2
            t_res, rest = first_token_latency(ids, resume_tokens=full[:cut])
            ttfr.append(t_res)
            identical = identical and (full[:cut] + rest == full)
    finally:
        eng.stop()

    p50_resume, p50_cold = pct(ttfr, 0.50), pct(ttft, 0.50)
    return {
        "metric": "engine_resume_ttfr_p50_ms",
        "value": p50_resume,
        "unit": "ms",
        "vs_baseline": (
            round(p50_cold / p50_resume, 3) if p50_resume else None
        ),
        "detail": {
            "requests": n_req,
            "prompt_tokens": prompt_len,
            "max_new": max_new,
            "model": ("1024d x 12L" if on_tpu else "tiny-cpu"),
            "uninterrupted_ttft_p50_ms": p50_cold,
            "uninterrupted_ttft_p99_ms": pct(ttft, 0.99),
            "resumed_ttfr_p50_ms": p50_resume,
            "resumed_ttfr_p99_ms": pct(ttfr, 0.99),
            "tokens_identical": identical,
            "baseline_is": (
                "the same request admitted cold on the same warm engine — "
                "TTFR/TTFT is the relative cost of the failover suffix "
                "prefill (prompt+committed) vs the original prompt prefill"
            ),
        },
    }


# --------------------------------------------------------------------------- #
# config 8 (beyond BASELINE): training hot-loop overlap — device prefetch +
# async metric drain + in-graph gradient accumulation (train/prefetch.py).
# Baseline = the same Trainer fully synchronous (prefetch_depth=0), the
# pre-overlap hot loop shape.
# --------------------------------------------------------------------------- #


def bench_train_overlap() -> dict:
    """Steps/sec through the REAL ``Trainer.fit`` hot loop, prefetch on vs.
    off and grad accumulation 1 vs 4 at the same effective global batch.

    The synthetic stream carries a fixed per-batch host cost (the
    decode/augment time a real input pipeline pays), so the prefetch number
    measures overlap of host work + H2D with the device step — not numpy
    speed. The overlap gauges from the same run show where the time went.
    """
    import jax
    import optax

    from kubeflow_tpu.core.mesh import MeshSpec
    from kubeflow_tpu.data.synthetic import (
        ClassPrototypeDataset,
        local_shard_iterator,
    )
    from kubeflow_tpu.models.mnist_cnn import MnistCNN, make_init_fn, make_loss_fn
    from kubeflow_tpu.train.loop import TrainConfig, Trainer

    host_cost_ms = 4.0
    steps, batch = 48, 64

    def run(prefetch_depth: int, accum: int) -> dict:
        model = MnistCNN()
        trainer = Trainer(
            init_params=make_init_fn(model),
            loss_fn=make_loss_fn(model),
            optimizer=optax.adam(1e-3),
            config=TrainConfig(
                mesh=MeshSpec.data_parallel(jax.device_count()),
                global_batch=batch,
                steps=steps,
                log_every=steps,  # one window = the whole steady-state run
                check_numerics="off",
                prefetch_depth=prefetch_depth,
                grad_accum_steps=accum,
            ),
        )
        data = local_shard_iterator(
            ClassPrototypeDataset(), batch, host_cost_ms=host_cost_ms
        )
        _, history = trainer.fit(data)
        last = history[-1]
        out = {
            k: round(float(last[k]), 3)
            for k in (
                "steps_per_sec", "data_stall_ms", "h2d_ms", "device_step_ms",
                "compile_ms",
            )
            if k in last
        }
        return out

    off = run(0, 1)
    on = run(4, 1)
    accum4 = run(4, 4)
    sps_on, sps_off = on["steps_per_sec"], off["steps_per_sec"]
    return {
        "metric": "train_overlap_steps_per_sec",
        "value": sps_on,
        "unit": "steps/s",
        "vs_baseline": round(sps_on / sps_off, 3) if sps_off else None,
        "detail": {
            "host_cost_ms_per_batch": host_cost_ms,
            "global_batch": batch,
            "steps": steps,
            "prefetch_off_accum1": off,
            "prefetch_on_accum1": on,
            "prefetch_on_accum4": accum4,
            "baseline_is": (
                "identical Trainer.fit with prefetch_depth=0 (inline input "
                "pipeline + synchronous metrics) — the pre-overlap hot loop"
            ),
        },
    }


# --------------------------------------------------------------------------- #


def main(argv: list[str] | None = None) -> int:
    device_benches = (
        bench_mnist, bench_resnet, bench_bert, bench_serving, bench_generate,
        bench_engine, bench_engine_decode, bench_engine_disagg,
        bench_engine_resume, bench_train_overlap,
    )
    # serving_load and katib are deliberately NOT in device_benches:
    # they measure the harness / the host-side search, not the chip
    all_benches = (
        bench_mnist, bench_resnet, bench_bert, bench_katib, bench_serving,
        bench_generate, bench_engine, bench_engine_decode,
        bench_engine_disagg, bench_engine_resume, bench_train_overlap,
        bench_serving_load,
    )
    # `python bench.py engine_decode [...]` runs just the named configs
    # (names = bench_* suffixes); no args runs the whole suite + headline
    argv = sys.argv[1:] if argv is None else argv
    by_name = {fn.__name__.removeprefix("bench_"): fn for fn in all_benches}
    if argv:
        unknown = [a for a in argv if a not in by_name]
        if unknown:
            print(
                f"unknown bench(es) {unknown}; choose from "
                f"{sorted(by_name)}", file=sys.stderr,
            )
            return 2
        selected = tuple(by_name[a] for a in argv)
    else:
        selected = all_benches
    import jax

    backend, devices = jax.devices()[0].platform, jax.device_count()
    on_device = [fn.__name__ for fn in selected if fn in device_benches]
    if on_device and backend != "tpu":
        print(
            f"device benches {on_device} need a TPU; JAX found platform "
            f"{backend!r} — a device metric is never measured elsewhere",
            file=sys.stderr,
        )
        return 1
    # persist XLA compiles so cold_start_s measures the cached path on any
    # run after the first — exactly what a restarted server pays
    from kubeflow_tpu.core.compcache import enable_compilation_cache

    enable_compilation_cache()
    results: list[dict] = []
    failed = 0
    for fn in selected:
        try:
            r = fn()
        except Exception as e:  # report every config, then exit non-zero
            failed += 1
            r = {
                "metric": fn.__name__,
                "value": None,
                "unit": "error",
                "vs_baseline": None,
                "detail": {"error": f"{type(e).__name__}: {e}"},
            }
        results.append(r)
        print(json.dumps(r), flush=True)

    if argv:
        # single-config runs emit their JSON lines, no headline
        return 1 if failed else 0

    bert = next(
        (r for r in results if r["metric"] == "bert_base_train_step_time"), None
    )
    mfu = (bert or {}).get("detail", {}).get("mfu_pct_vs_v5e_peak")
    headline = {
        "metric": "bert_base_train_mfu",
        "value": mfu,
        "unit": "%",
        "vs_baseline": (bert or {}).get("vs_baseline"),
        "detail": {
            "backend": backend,
            "devices": devices,
            "note": "MFU = analytic matmul FLOPs / v5e bf16 peak (197 TFLOP/s)",
            "all_metrics": {
                r["metric"]: {
                    "value": r["value"],
                    "unit": r["unit"],
                    "vs_baseline": r["vs_baseline"],
                    **{
                        k: v
                        for k, v in r.get("detail", {}).items()
                        if k
                        in (
                            "mfu_pct_vs_v5e_peak",
                            "iqr_ms",
                            "steady_state_drift",
                            "cold_start_s",
                            "rest_p99_ms",
                            "grpc_p50_ms",
                            "ms_per_decode_step",
                            "error",
                        )
                    },
                }
                for r in results
            },
        },
    }
    print(json.dumps(headline), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
