"""JAXJob MNIST worker — BASELINE config 1 (`pytorchjob-mnist` analog).

The reference example does ``dist.init_process_group(backend); DDP(model)``
inside a PyTorchJob pod (SURVEY.md §3.1 hot loop). The JAXJob version:
bootstrap ``jax.distributed`` from the env contract the orchestrator wrote,
build a data-parallel mesh over ALL global devices, and run the jitted SPMD
step — the gradient allreduce is XLA-emitted (ICI on TPU; gloo between CPU
sim processes, coincidentally the very backend of BASELINE config 1).

Run under the orchestrator:
    JobSpec(replicas={"worker": ReplicaSpec(replicas=N,
        command=(python, "-m", "kubeflow_tpu.examples.mnist", ...))})
or standalone on any host with devices.
"""

from __future__ import annotations

import argparse

import optax


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--global-batch", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--checkpoint-dir", type=str, default=None)
    p.add_argument("--checkpoint-every", type=int, default=50)
    p.add_argument(
        "--checkpoint-sync", action="store_true",
        help="synchronous saves: every saved step is durable (with its "
             "sha256 manifest) before the next step runs — what the chaos "
             "kill-mid-train tests rely on for exact-step resume",
    )
    p.add_argument("--no-resume", action="store_true")
    p.add_argument("--metrics-logdir", type=str, default=None)
    p.add_argument(
        "--grad-accum-steps", type=int, default=1,
        help="in-graph microbatch accumulation (one optimizer update)",
    )
    p.add_argument(
        "--prefetch-depth", type=int, default=2,
        help="device-prefetch depth; 0 runs the input pipeline inline",
    )
    args = p.parse_args(argv)

    # Rendezvous BEFORE any device access (the torchrun-analog moment).
    from kubeflow_tpu.core.distributed import initialize_from_env

    cfg = initialize_from_env()

    import jax

    from kubeflow_tpu.core.mesh import MeshSpec
    from kubeflow_tpu.data.synthetic import (
        ClassPrototypeDataset,
        local_shard_iterator,
    )
    from kubeflow_tpu.models.mnist_cnn import MnistCNN, make_init_fn, make_loss_fn
    from kubeflow_tpu.train.checkpoint import CheckpointConfig
    from kubeflow_tpu.train.loop import TrainConfig, Trainer

    print(
        f"process {cfg.process_id}/{cfg.num_processes}: "
        f"{jax.local_device_count()} local / {jax.device_count()} global "
        f"{jax.default_backend()} devices "
        f"(device_kind={jax.devices()[0].device_kind})",
        flush=True,
    )

    model = MnistCNN()
    trainer = Trainer(
        init_params=make_init_fn(model),
        loss_fn=make_loss_fn(model),
        optimizer=optax.adam(args.lr),
        config=TrainConfig(
            mesh=MeshSpec.data_parallel(jax.device_count()),
            global_batch=args.global_batch,
            steps=args.steps,
            log_every=args.log_every,
            seed=args.seed,
            checkpoint=(
                CheckpointConfig(
                    directory=args.checkpoint_dir,
                    save_every_steps=args.checkpoint_every,
                    async_save=not args.checkpoint_sync,
                )
                if args.checkpoint_dir
                else None
            ),
            resume=not args.no_resume,
            metrics_logdir=args.metrics_logdir,
            grad_accum_steps=args.grad_accum_steps,
            prefetch_depth=args.prefetch_depth,
        ),
    )
    # Factory form: on checkpoint resume the stream continues at the
    # restored step instead of replaying batch 0.
    data = lambda start_step: local_shard_iterator(  # noqa: E731
        ClassPrototypeDataset(seed=args.seed),
        args.global_batch,
        start_step=start_step,
    )
    _state, history = trainer.fit(data)

    if jax.process_index() == 0 and history:
        first, last = history[0], history[-1]
        print(
            f"final_loss={last['loss']:.6g} final_accuracy={last['accuracy']:.6g} "
            f"steps_per_sec={last['steps_per_sec']:.6g}",
            flush=True,
        )
        if not (last["loss"] < first["loss"] or last["accuracy"] > 0.9):
            print("WARNING: loss did not improve", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
