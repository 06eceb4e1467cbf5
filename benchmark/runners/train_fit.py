"""A training cell: ``Trainer.fit`` as a user calls it — prefetch, the
metrics drain and donation all running — on synthetic tokens from the seed.

Two calls to ``fit``. The first (``warm_steps`` steps) compiles or loads the
step and gives the step time; it is set-up. The second runs the number of
steps that fills ``--seconds`` at that step time — a fixed amount of work,
so a run does not end on a fraction of a step — and is measured from the
moment its first step's result is ready to the moment its last one's is,
through ``fit(hooks=)`` on the drain thread. ``tokens_per_s`` is the tokens
of those steps over that time.

Correctness, after the window: every step's loss finite (the trainer's own
alarm raises otherwise), and ``check_first_batch`` on the initial weights
and the first batch.
"""

from __future__ import annotations

import math
import sys
import time

import numpy as np

from benchmark import harness
from benchmark.evidence import Evidence
from benchmark.manifest import plugin
from benchmark.peaks import device_peaks

#: Step 0's loss as the trainer reported it against the float32 reference's
#: mean over the same positions. On seeded weights every logit is about
#: N(0, 1) whatever the layers compute, so this scalar cannot tell a wrong
#: layer from a right one (it is ln V + 0.5 either way): it ties the trainer's
#: batch, mask and reduction to the reference's, no more. Eleven chip runs of
#: PR 22 differed by 0.1e-5 to 2.5e-5 relative; the limit is ten times that.
LOSS_RTOL = 3e-4
#: what ``check_first_batch`` compares, each against ``limits[<name>]``
CHECKED = ("nll_err_max", "nll_err_mean", "loss_rel_err")


def run(ctx) -> Evidence:
    t_enter = time.perf_counter()
    import jax
    import optax

    from kubeflow_tpu.core.mesh import MeshSpec
    from kubeflow_tpu.parallel.sharding import transformer_rules
    from kubeflow_tpu.train.loop import TrainConfig, Trainer
    from kubeflow_tpu.train.metrics import MetricWriter

    t_imported = time.perf_counter()
    cfg, mix = ctx.config, ctx.traffic
    family = plugin("families", cfg["family"])
    setup = family.train_setup(cfg, mix, ctx.seed)
    train = cfg.get("train", {})
    mesh_axes = train.get("mesh")
    mesh = MeshSpec(**mesh_axes) if mesh_axes else MeshSpec.data_parallel(ctx.cell["chips"])
    sharded = mesh_axes is not None

    def trainer_for(steps: int) -> Trainer:
        return Trainer(
            init_params=setup["init_params"], loss_fn=setup["loss_fn"],
            optimizer=optax.adamw(train.get("learning_rate", 1e-4)),
            config=TrainConfig(
                mesh=mesh, global_batch=mix["global_batch"], steps=steps,
                log_every=1, seed=ctx.seed, handle_sigterm=False,
            ),
            param_spec_fn=transformer_rules() if sharded else None,
        )

    def fit(steps: int, hooks):
        trainer = trainer_for(steps)
        # per-step lines go to stderr: stdout's last line is the result
        writer = MetricWriter(None, stdout=sys.stderr)
        try:
            return trainer, trainer.fit(setup["data"], writer=writer, hooks=hooks)[1]
        finally:
            writer.close()

    # -- set-up: compile or load, and the step time ---------------------- #
    warm_steps = mix["warm_steps"]
    t_run = time.perf_counter()
    with harness.annotate("bench.warm_fit"):
        trainer, warm = fit(warm_steps, None)
    t_warm = time.perf_counter()
    first_loss = warm[0]["loss"]
    step_ms = np.median([h["device_step_ms"] for h in warm[1:]])
    steps = 1 + max(2, math.ceil(ctx.seconds * 1e3 / step_ms))

    # -- the window ------------------------------------------------------ #
    ready: list[float] = []
    hook_steps: list[dict] = []
    compiled: list[float] = []
    live: list[int] = []
    profiler = None
    if ctx.profiled:
        profiler = harness.TraceWindow(
            ctx.cell["name"], after=mix["trace_after_s"], seconds=mix["trace_s"]
        )

    def hook(step, metrics):
        ready.append(time.perf_counter())
        if step == 1:
            # the window opens here: the measured fit's own first step
            # re-traces and loads its program, which is not steady state
            compiled.append(harness.compile_stats()["programs"])
            if profiler is not None:
                profiler.start()
        else:
            hook_steps.append(dict(metrics))
            live.append(harness.live_bytes())   # later steps are running

    _, history = fit(steps, [hook])
    compiles_in_window = harness.compile_stats()["programs"] - compiled[0]
    window_s = ready[-1] - ready[0]
    setup_s = ready[0] - ctx.t_process
    done_steps = len(ready) - 1
    tokens_per_s = done_steps * setup["tokens_per_step"] / window_s
    reduction = profiler.reduction() if profiler is not None else None
    peak_bytes = harness.memory_peak_bytes(live)

    # -- correctness, outside the window --------------------------------- #
    losses = [h["loss"] for h in history]
    state0 = trainer.init_state()
    params0, rng0 = state0.params, jax.random.fold_in(state0.rng, 0)
    del state0                                  # the optimizer state goes
    batch0 = next(iter(setup["data"](0)))
    check = check_first_batch(setup, trainer, params0, batch0, rng0, first_loss, mix["check"])
    del params0
    correct = bool(
        len(losses) == steps and np.all(np.isfinite(losses))
        and check["ok"] and compiles_in_window == 0
    )

    peaks = device_peaks(ctx.device["kind"])
    stats = harness.compile_stats()
    ev = Evidence(
        cell=ctx.cell, hook_steps=hook_steps,
        trace=reduction, attempted=steps, failed=steps - len(losses),
        correct=correct,
        check={
            **{k: check[k] for k in ("positions", *CHECKED)},
            **{f"{k}_limit": check["limits"][k] for k in CHECKED},
            "losses_not_finite": int(np.sum(~np.isfinite(losses))), "losses_not_finite_limit": 0,
            "compiles_in_window": compiles_in_window, "compiles_in_window_limit": 0,
        },
    )
    ev.numbers.update({
        "e2e.tokens_per_s": tokens_per_s,
        "e2e.setup_s": setup_s,
        "context.chips": float(ctx.cell["chips"]),
        "context.tokens_per_step": float(setup["tokens_per_step"]),
        "context.flops_per_token": setup["flops_per_token"],
        "context.peak_flops_per_chip": peaks["bf16_flops"],
        "context.peak_hbm_bytes_per_s": peaks["hbm_bytes_per_s"],
        "context.attention_train_flops": setup["attn_flops"],
        "context.attention_train_bytes": setup["attn_bytes"],
        "device.memory_peak_bytes": float(peak_bytes),
        "xla.programs": stats["programs"],
        "xla.cache_hits": stats["cache_hits"],
        "xla.compile_seconds": stats["seconds"],
        "xla.compiles_in_window": float(compiles_in_window),
    })
    ev.notes.update({
        "window_s": window_s, "steps_in_window": done_steps,
        "warm_step_ms": float(step_ms), "check": check,
        "last_loss": losses[-1] if losses else None,
        "setup_parts_s": {
            "start_to_runner": t_enter - ctx.t_process,
            "program_imports": t_imported - t_enter,
            "family_setup": t_run - t_imported, "warm_fit": t_warm - t_run,
            "warm_fit_first_step": warm[0].get("compile_ms", 0.0) / 1e3,
            "measured_fit_to_first_step": ready[0] - t_warm,
            "measured_fit_first_step": history[0].get("compile_ms", 0.0) / 1e3,
        },
        "memory_stats": harness.memory_stats(),
    })
    return ev


def check_first_batch(setup, trainer, params0, batch0, rng0, first_loss, limits) -> dict:
    """The program's forward pass against the plain float32 reference, on
    the initial weights and the whole first batch, position by position:
    the cross-entropy of each position's target under the program's logits
    (its model as the trainer runs it: activation type, kernels, remat,
    sharding) may differ from the reference's by at most
    ``limits["nll_err_max"]`` anywhere and ``limits["nll_err_mean"]`` on
    average. The limits are data of the cell (its traffic file), about ten
    times the gap measured there between bf16 and float32: a dropped
    attention term, a wrong mask or a missing window moves single positions
    by whole units (``tests/benchmark/test_benchmark_runners.py``). Then the
    scalar: ``first_loss`` against the reference's mean (``LOSS_RTOL``)."""
    import jax

    inputs, targets, weight = setup["check_batch"](batch0, rng0)
    want = setup["reference_nll"](params0, inputs, targets)          # (B, S)
    with jax.set_mesh(trainer.mesh):
        got = np.asarray(jax.jit(program_nll(setup["forward"]))(
            params0, jax.device_put(inputs, trainer.batch_sharding),
            jax.device_put(targets, trainer.batch_sharding),
        ))
    err = np.abs(got.astype(np.float64) - want)
    ref_loss = float((want * weight).sum() / weight.sum())
    out = {
        "positions": int(err.size),
        "nll_err_max": float(err.max()), "nll_err_mean": float(err.mean()),
        "nll_std": float(want.std()),
        "first_loss": first_loss, "reference_first_loss": ref_loss,
        "loss_rel_err": abs(first_loss - ref_loss) / abs(ref_loss),
        "limits": dict(limits, loss_rel_err=LOSS_RTOL),
    }
    out["ok"] = all(out[k] <= out["limits"][k] for k in CHECKED)
    return out


def program_nll(forward):
    """(params, inputs, targets) → the cross-entropy (B, S) of each
    position's target under the program's logits."""
    import jax
    import jax.numpy as jnp

    def nll(params, inputs, targets):
        logp = jax.nn.log_softmax(forward(params, inputs).astype(jnp.float32), -1)
        return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]

    return nll
