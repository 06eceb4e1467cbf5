"""The SPMD training loop: pjit-sharded steps over a MeshSpec.

The data-plane analog of the reference's ``DDP(model); loss.backward();
allreduce; optimizer.step()`` hot loop (SURVEY.md §3.1): here the whole step
is ONE jitted SPMD program — XLA emits the gradient psum onto ICI from the
sharding layout (params replicated/sharded per rules, batch sharded on the
data axes), so there is no explicit allreduce call to schedule or bucket.
"""

from __future__ import annotations

import dataclasses
import logging
import signal as _signal
import threading
import time
from collections.abc import Iterator
from typing import Any, Callable, Iterable, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from flax.training import train_state
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeflow_tpu.core.mesh import Axis, MeshSpec, build_mesh, per_device_batch
from kubeflow_tpu.core.parts import OPTIMIZER
from kubeflow_tpu.train.checkpoint import CheckpointConfig, Checkpointer
from kubeflow_tpu.train.metrics import MetricWriter

logger = logging.getLogger(__name__)

#: batch pytrees are sharded over the data-like axes on dim 0.
BATCH_SPEC = P((Axis.DATA, Axis.FSDP))

#: the container convention for SIGTERM death (128+15) — retryable under
#: ``RestartPolicy.EXIT_CODE``, so a preempted gang restarts and resumes.
PREEMPTED_EXIT_CODE = 143


class Preempted(SystemExit):
    """Raised out of ``fit`` after a preemption notice was honored: the
    final checkpoint is on disk and the process should exit ``code`` (143,
    a retryable infra code under ``RestartPolicy.EXIT_CODE``)."""

    def __init__(self, step: int, code: int = PREEMPTED_EXIT_CODE):
        super().__init__(code)
        self.step = step


class TrainState(train_state.TrainState):
    """flax TrainState + a dropout/noise RNG folded per step."""

    rng: jax.Array


@dataclasses.dataclass
class TrainConfig:
    mesh: MeshSpec
    global_batch: int
    steps: int
    log_every: int = 10
    seed: int = 0
    checkpoint: CheckpointConfig | None = None
    #: True/"auto": restore from the newest checkpoint step whose sha256
    #: manifest verifies, walking past corrupt steps (train/checkpoint.py);
    #: False: always start from step 0.
    resume: bool | str = True
    metrics_logdir: str | None = None
    #: install a SIGTERM handler for the duration of ``fit`` (main thread
    #: only — elsewhere the signal machinery is unavailable and the flag
    #: can still be set via ``Trainer.request_preemption``). On delivery
    #: the loop finishes the in-flight step, force-saves a final
    #: preemption checkpoint, and raises ``Preempted`` (SystemExit 143 —
    #: retryable under ``RestartPolicy.EXIT_CODE``, so the orchestrator
    #: restarts the gang and training resumes at the exact next step).
    handle_sigterm: bool = True
    donate_state: bool = True
    #: in-graph gradient accumulation: the jitted step scans over
    #: ``grad_accum_steps`` microbatches (one optimizer update, donated
    #: carry) so ``global_batch`` scales past HBM limits with unchanged
    #: numerics — losses match accum=1 to fp32 tolerance for equal-size
    #: microbatches (mean of microbatch means == full-batch mean).
    grad_accum_steps: int = 1
    #: device-prefetch depth (train/prefetch.py): how many already-placed
    #: global batches the background producer keeps ahead of the step
    #: stream. 0 = fully inline (no thread). Each buffered batch holds
    #: device memory, so this is an HBM budget knob too.
    prefetch_depth: int = 2
    #: numerics discipline (SURVEY.md §5.2):
    #: - "metrics"  (default): the MetricWriter raises NonFiniteMetricError
    #:   the first time a logged metric is NaN/inf — zero overhead on the
    #:   hot path, detection within log_every steps.
    #: - "checkify": every step runs under jax.experimental.checkify
    #:   float_checks — the raise names the exact op and source line that
    #:   produced the first NaN/inf, at ~2x step cost. For debugging runs.
    #: - "off": no checks (bench/microbenchmark mode).
    check_numerics: str = "metrics"
    #: sets jax_debug_nans for the whole process (eager-level NaN isolation;
    #: orthogonal to checkify — use when the NaN is outside the step).
    debug_nans: bool = False

    def __post_init__(self) -> None:
        if self.check_numerics not in ("off", "metrics", "checkify"):
            # a typo here must not silently degrade to default behavior
            raise ValueError(
                f"check_numerics={self.check_numerics!r}; expected "
                "'off', 'metrics', or 'checkify'"
            )
        if self.grad_accum_steps < 1:
            raise ValueError(
                f"grad_accum_steps must be >= 1, got {self.grad_accum_steps}"
            )
        if self.prefetch_depth < 0:
            raise ValueError(
                f"prefetch_depth must be >= 0, got {self.prefetch_depth}"
            )
        if not isinstance(self.resume, bool) and self.resume != "auto":
            # a typo must not silently disable (or mis-enable) resume
            raise ValueError(
                f"resume={self.resume!r}; expected True, False, or 'auto'"
            )
        if self.global_batch % self.grad_accum_steps:
            raise ValueError(
                f"global batch {self.global_batch} not divisible by "
                f"grad_accum_steps={self.grad_accum_steps}"
            )


class Trainer:
    """Generic SPMD trainer.

    ``loss_fn(params, batch, rng) -> (loss, aux_dict)`` — differentiated on
    arg 0. ``init_params(rng) -> params``. ``state_spec_fn`` maps the param
    tree to PartitionSpecs (None = fully replicated = pure DP); FSDP/TP rules
    from ``kubeflow_tpu.parallel`` plug in here.
    """

    def __init__(
        self,
        *,
        init_params: Callable[[jax.Array], Any],
        loss_fn: Callable[[Any, Any, jax.Array], tuple[jax.Array, Mapping[str, Any]]],
        optimizer: Any,
        config: TrainConfig,
        param_spec_fn: Callable[[Any], Any] | None = None,
    ):
        from kubeflow_tpu.core.compcache import enable_compilation_cache

        enable_compilation_cache()  # restarts skip the train-step compile
        self.config = config
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.init_params_fn = init_params
        self.param_spec_fn = param_spec_fn
        self.mesh: Mesh = build_mesh(config.mesh)
        self.batch_sharding = NamedSharding(self.mesh, BATCH_SPEC)
        self.repl = NamedSharding(self.mesh, P())
        self._step_fn = None
        self._state_sharding = None
        #: preemption notice (SIGTERM or an explicit call): the loop checks
        #: it between steps and performs the graceful-exit protocol.
        self._preempt = threading.Event()

    def request_preemption(self) -> None:
        """Deliver a preemption notice in-process (what the SIGTERM handler
        calls): the loop saves a final checkpoint and raises ``Preempted``
        at the next step boundary. Safe from any thread."""
        self._preempt.set()

    # ------------------------------------------------------------------ #

    def init_state(self) -> TrainState:
        """Initialize params ON the mesh with their target shardings (jit of
        init so large params materialize sharded, never on one host)."""
        rng = jax.random.PRNGKey(self.config.seed)

        def mk(rng):
            params = self.init_params_fn(rng)
            return TrainState.create(
                apply_fn=None,
                params=params,
                tx=self.optimizer,
                rng=rng,
            )

        # set_mesh: models read the context mesh for activation sharding
        # constraints and shard_map attention (ring/ulysses/flash).
        with jax.set_mesh(self.mesh):
            if self.param_spec_fn is None:
                out_shardings = self.repl
            else:
                abstract = jax.eval_shape(mk, rng)
                specs = self._specs_for(abstract)
                out_shardings = jax.tree_util.tree_map(
                    lambda s: NamedSharding(self.mesh, s), specs
                )
            # SPMD determinism contract (SURVEY.md §5.2): the same seed
            # must yield the same params on EVERY mesh layout. The legacy
            # threefry lowering is not sharding-invariant — jitted init
            # with sharded out_shardings on a hybrid (data x fsdp) mesh
            # draws different values than the replicated/pure layouts; the
            # partitionable lowering derives each element's bits from its
            # global index alone. Scoped to THIS trace/compile (restored
            # after) so the process-wide PRNG stream is untouched for
            # everything else running in-process.
            prev = jax.config.jax_threefry_partitionable
            jax.config.update("jax_threefry_partitionable", True)
            try:
                state = jax.jit(mk, out_shardings=out_shardings)(rng)
            finally:
                jax.config.update("jax_threefry_partitionable", prev)
        self._state_sharding = jax.tree_util.tree_map(lambda x: x.sharding, state)
        return state

    def _specs_for(self, abstract_state) -> Any:
        """PartitionSpec tree for the full TrainState: params per rules,
        optimizer-state subtrees that mirror the params structure get the
        same specs (ZeRO-style colocation), everything else replicated.

        Matching is *structural* (a subtree with the params' treedef), not
        by shape/dtype — same-shaped params with different specs must not
        collide."""
        param_specs = jax.tree_util.tree_map(
            lambda s: s if isinstance(s, P) else (P() if s is None else P(*s)),
            self.param_spec_fn(abstract_state.params),
            is_leaf=lambda x: x is None or isinstance(x, (P, tuple)),
        )
        if jax.tree_util.tree_structure(param_specs) != jax.tree_util.tree_structure(
            abstract_state.params
        ):
            raise ValueError(
                "param_spec_fn must return a tree with the params' structure"
            )
        params_def = jax.tree_util.tree_structure(abstract_state.params)

        def is_params_like(node) -> bool:
            try:
                return jax.tree_util.tree_structure(node) == params_def
            except Exception:  # noqa: BLE001 — unhashable/odd nodes aren't params
                return False

        return jax.tree_util.tree_map(
            lambda node: (
                param_specs
                if is_params_like(node)
                else jax.tree_util.tree_map(lambda _: P(), node)
            ),
            abstract_state,
            is_leaf=is_params_like,
        )

    # ------------------------------------------------------------------ #

    def _build_step(self, state: TrainState):
        loss_fn = self.loss_fn
        accum = self.config.grad_accum_steps
        micro_sharding = NamedSharding(self.mesh, P(None, *BATCH_SPEC))

        def grads_of(params, batch, rng):
            return jax.value_and_grad(loss_fn, has_aux=True)(params, batch, rng)

        def step(state: TrainState, batch):
            rng = jax.random.fold_in(state.rng, state.step)
            if accum == 1:
                (loss, aux), grads = grads_of(state.params, batch, rng)
            else:
                # [B, ...] -> [accum, B/accum, ...]: microbatches stay
                # sharded over the data axes on their own dim 0, the scan
                # axis is replicated — one optimizer update at the end, so
                # numerics match accum=1 (mean of equal-size microbatch
                # means == full-batch mean) while peak activation memory
                # drops by ~accum.
                micro = jax.tree_util.tree_map(
                    lambda x: jax.lax.with_sharding_constraint(
                        x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
                        micro_sharding,
                    ),
                    batch,
                )
                params = state.params

                def body(carry, xs):
                    g_acc, loss_acc, aux_acc = carry
                    mb, i = xs
                    (loss, aux), grads = grads_of(
                        params, mb, jax.random.fold_in(rng, i)
                    )
                    carry = (
                        jax.tree_util.tree_map(jnp.add, g_acc, grads),
                        loss_acc + loss,
                        jax.tree_util.tree_map(jnp.add, aux_acc, aux),
                    )
                    return carry, None

                # microbatch 0 seeds the carry (no zeros-tree dtype
                # guessing); the scan covers 1..accum-1 with donated carry
                (loss_0, aux_0), g_0 = grads_of(
                    params,
                    jax.tree_util.tree_map(lambda x: x[0], micro),
                    jax.random.fold_in(rng, 0),
                )
                (g_sum, loss_sum, aux_sum), _ = jax.lax.scan(
                    body,
                    (g_0, loss_0, aux_0),
                    (
                        jax.tree_util.tree_map(lambda x: x[1:], micro),
                        jnp.arange(1, accum),
                    ),
                )
                grads = jax.tree_util.tree_map(lambda g: g / accum, g_sum)
                loss = loss_sum / accum
                aux = jax.tree_util.tree_map(lambda a: a / accum, aux_sum)
            with jax.named_scope(OPTIMIZER):
                new_state = state.apply_gradients(grads=grads)
            metrics = {"loss": loss, **aux}
            return new_state, metrics

        state_shardings = self._state_sharding
        if self.config.check_numerics == "checkify":
            from jax.experimental import checkify

            # No donation and inferred shardings: a failed step must leave
            # the caller's state alive so the error can be reported and the
            # run resumed from checkpoint.
            checked = jax.jit(
                checkify.checkify(step, errors=checkify.float_checks)
            )

            def run(state: TrainState, batch):
                err, out = checked(state, batch)
                checkify.check_error(err)  # located: op + source line
                return out

            return run
        return jax.jit(  # kft: noqa[jax-sync] — fit-owned donation: restored trees are re-homed through the non-donating identity before the first donated call
            step,
            in_shardings=(state_shardings, self.batch_sharding),
            out_shardings=(state_shardings, self.repl),
            donate_argnums=(0,) if self.config.donate_state else (),
        )

    def global_batch_array(self, local_batch) -> Any:
        """Process-local numpy batch shards → one global sharded pytree.

        Thread-safe: the device prefetcher calls this from its producer
        thread (explicit NamedSharding, no ambient-mesh dependence), so the
        H2D copy overlaps the running step.
        """
        return jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(
                self.batch_sharding, np.asarray(x)  # kft: noqa[jax-sync] — operand is the host-resident input batch, pre-placement; no device value exists yet
            ),
            local_batch,
        )

    def local_batch_size(self, process_count: int | None = None) -> int:
        n = jax.process_count() if process_count is None else process_count
        if self.config.global_batch % n:
            raise ValueError(
                f"global batch {self.config.global_batch} not divisible by "
                f"{n} processes — floor division would silently drop "
                f"{self.config.global_batch % n} examples per step"
            )
        return self.config.global_batch // n

    # ------------------------------------------------------------------ #

    def fit(
        self,
        data: Iterator[Any] | Iterable[Any] | Callable[[int], Iterator[Any]],
        *,
        writer: MetricWriter | None = None,
        hooks: list[Callable[[int, Mapping[str, float]], None]] | None = None,
    ) -> tuple[TrainState, list[dict]]:
        """Train for ``config.steps``.

        ``data`` is ideally a *factory* ``start_step -> iterator`` so that a
        checkpoint resume continues the stream where training resumes rather
        than replaying batch 0; a plain iterator is accepted for
        non-resuming runs.
        """
        cfg = self.config
        per_device_batch(cfg.global_batch, cfg.mesh)  # validate divisibility
        # microbatches must also land evenly on the batch partitions, and
        # the per-process shard must be whole (no silent truncation)
        per_device_batch(cfg.global_batch // cfg.grad_accum_steps, cfg.mesh)
        self.local_batch_size()
        if cfg.debug_nans:
            jax.config.update("jax_debug_nans", True)
        own_writer = writer is None
        writer = writer or MetricWriter(
            cfg.metrics_logdir,
            is_writer=jax.process_index() == 0,
            nan_alarm=cfg.check_numerics != "off",
        )

        # Liveness: when launched by the orchestrator, beat automatically so
        # the heartbeat supervisor can tell "compiling/training" from "hung"
        # (SURVEY.md §5.3). No-op outside a gang.
        from kubeflow_tpu.obs.heartbeat import HeartbeatWriter

        hb = HeartbeatWriter.from_env()
        if hb is not None:
            hb.start()

        # Preemption notice: SIGTERM (a slice being reclaimed) sets a flag
        # the loop honors at the next step boundary — final checkpoint,
        # then exit 143 so RestartPolicy.EXIT_CODE treats it as retryable
        # infra. Signal handlers only install on the main thread; elsewhere
        # (a fit driven from a server thread) request_preemption() remains
        # the delivery path.
        self._preempt.clear()
        prev_sigterm = None
        sigterm_installed = False
        if (
            cfg.handle_sigterm
            and threading.current_thread() is threading.main_thread()
        ):
            def _on_sigterm(signum, frame):  # noqa: ARG001
                logger.warning(
                    "SIGTERM received: taking a preemption checkpoint, "
                    "then exiting %d", PREEMPTED_EXIT_CODE,
                )
                self._preempt.set()

            try:
                prev_sigterm = _signal.signal(_signal.SIGTERM, _on_sigterm)
                sigterm_installed = True
            except (ValueError, OSError):  # exotic embeddings
                sigterm_installed = False

        state = self.init_state()
        ckpt: Checkpointer | None = None
        start_step = 0
        if cfg.checkpoint is not None:
            ckpt = Checkpointer(cfg.checkpoint)
            if cfg.resume and ckpt.latest_step() is not None:
                # Walks back to the newest step whose sha256 manifest
                # verifies — a corrupt latest checkpoint costs one save
                # interval, not the run (train/checkpoint.py).
                state = ckpt.restore(state)
                # Re-home the restored tree into XLA-owned buffers (a
                # non-donating jitted identity is a sharded copy). Orbax
                # hands back arrays whose buffers the CPU backend aliases
                # from host memory; donating those into the first step makes
                # XLA reuse/free memory it doesn't own — deterministic heap
                # corruption the moment anything syncs on that step's
                # outputs (which the metric drain now does every step).
                state = jax.jit(lambda s: s)(state)
                start_step = int(jax.device_get(state.step))
                logger.info("resumed from checkpoint at step %d", start_step)
                if jax.process_index() == 0:
                    # machine-readable resume marker for supervisors and
                    # the chaos harness (exact-step resume assertions)
                    print(f"resume_step={start_step}", flush=True)
        if callable(data) and not hasattr(data, "__next__"):
            it = iter(data(start_step))
        else:
            if start_step and not isinstance(data, Iterator):
                logger.warning(
                    "resuming at step %d with a plain iterator: the data "
                    "stream restarts from its beginning; pass a "
                    "start_step->iterator factory for a faithful resume",
                    start_step,
                )
            it = iter(data)

        step_fn = self._build_step(state)
        history: list[dict] = []
        t_last = time.perf_counter()
        last_logged = start_step
        try:
            with jax.set_mesh(self.mesh):
                return self._fit_loop(
                    state, step_fn, it, ckpt, writer, hooks, history,
                    start_step, t_last, last_logged, hb,
                )
        finally:
            if sigterm_installed:
                try:
                    _signal.signal(
                        _signal.SIGTERM,
                        prev_sigterm if prev_sigterm is not None
                        else _signal.SIG_DFL,
                    )
                except (ValueError, OSError):
                    pass
            if hb is not None:
                hb.stop()
            if ckpt is not None:
                ckpt.close()  # preemption path: blocks until durable
            if own_writer:
                writer.close()

    def _fit_loop(
        self, state, step_fn, it, ckpt, writer, hooks, history,
        start_step, t_last, last_logged, hb=None,
    ):
        """The overlapped hot loop (train/prefetch.py):

        - input: a bounded producer thread assembles + places batches
          ``prefetch_depth`` ahead, so ``next(it)`` + H2D never sit between
          step dispatches;
        - output: every step's *device* metrics go to a drain thread that
          blocks on them there — the loop thread never syncs on the step
          stream, and the writer's NaN alarm re-raises here via ``poll()``
          with bounded lag;
        - timing: the first step is blocked on explicitly (``compile_ms``),
          and the rate clock re-stamps at its readiness so the first logged
          ``steps_per_sec`` window measures steady state, not XLA.
        """
        from kubeflow_tpu.train.prefetch import MetricsDrain, make_fetcher

        cfg = self.config
        fetcher = make_fetcher(
            it, self.global_batch_array, depth=cfg.prefetch_depth
        )
        # the drain stamps every completed step into the heartbeat file, so
        # the supervisor's progress watchdog sees real trainer advancement
        # (not just thread liveness) without touching the hot loop thread
        drain = MetricsDrain(
            writer, history=history, hooks=hooks, heartbeat=hb
        )
        compile_ms = None
        try:
            for step in range(start_step, cfg.steps):
                drain.poll()  # bounded-lag NaN alarm / drain-error surface
                if self._preempt.is_set():
                    self._preemption_save(ckpt, state, step)
                    raise Preempted(step)
                batch = next(fetcher)
                if compile_ms is None:
                    # block on step 1 so the compile is measured apart; the
                    # drain's rate clock starts at this step's readiness, so
                    # no later steps_per_sec window includes it. Sync via a
                    # HOST TRANSFER of a metric scalar, not
                    # block_until_ready: a transfer cannot complete before
                    # the compute producing it (the bench.py contract), and
                    # block_until_ready on this jaxlib corrupts the heap
                    # when the donated state came from an Orbax restore.
                    t0 = time.perf_counter()
                    state, metrics = step_fn(state, batch)
                    np.asarray(jax.tree_util.tree_leaves(metrics)[0])  # kft: noqa[jax-sync] — the one sanctioned sync: compile measurement via single-leaf host transfer, once, before steady state
                    compile_ms = (time.perf_counter() - t0) * 1e3
                else:
                    state, metrics = step_fn(state, batch)
                if ckpt is not None:
                    ckpt.save(step + 1, state)
                is_log = (step + 1) % cfg.log_every == 0 or step + 1 == cfg.steps
                extra = None
                if is_log:
                    now = time.perf_counter()
                    # dispatch-side rate (compile-inclusive, like the old
                    # loop): the drain only falls back to it for the
                    # degenerate first window where no ready-to-ready
                    # interval exists yet
                    elapsed = max(now - t_last, 1e-9)
                    extra = {
                        "fallback_steps_per_sec": max(
                            step + 1 - last_logged, 1
                        ) / elapsed,
                        **fetcher.window_stats(),
                    }
                    if compile_ms:
                        # first log boundary: report the compile apart,
                        # exactly once
                        extra["compile_ms"] = compile_ms
                        compile_ms = 0.0
                    t_last, last_logged = now, step + 1
                drain.put(step + 1, metrics, log=is_log, extra=extra)
            drain.close()  # flush; surfaces a pending NaN alarm
        finally:
            fetcher.close()
            drain.shutdown()  # idempotent, no-raise (exception paths)
            if ckpt is not None:
                self._final_save(ckpt, state)
        drain.poll()
        return state, history

    @staticmethod
    def _preemption_save(
        ckpt: Checkpointer | None, state: TrainState, step: int
    ) -> None:
        """The graceful half of preemption: force-save the current state
        (the loop-top invariant is ``state.step == step``) so the restarted
        gang resumes at exactly ``step + 1``. Durability is guaranteed by
        ``ckpt.close()`` in ``fit``'s finally before the exit code lands."""
        if ckpt is not None and ckpt.latest_step() != step:
            ckpt.save(step, state, force=True)
        logger.warning(
            "preempted at step %d: final checkpoint %s; exiting %d",
            step,
            "saved" if ckpt is not None else "unavailable (no checkpoint "
            "config)",
            PREEMPTED_EXIT_CODE,
        )

    @staticmethod
    def _final_save(ckpt: Checkpointer, state: TrainState) -> None:
        """Best-effort final checkpoint; with donated buffers the state may
        be dead if the last step raised — never mask the original error."""
        leaves = jax.tree_util.tree_leaves(state)
        if any(
            isinstance(x, jax.Array) and x.is_deleted() for x in leaves
        ):
            logger.warning("skipping final checkpoint: state buffers donated "
                           "to a failed step")
            return
        final_step = int(jax.device_get(state.step))
        if ckpt.latest_step() != final_step:
            ckpt.save(final_step, state, force=True)
