"""kserve-bert — BASELINE config 5 / north-star second example.

The reference flow (SURVEY.md §3.3): `InferenceService(predictor:
huggingface, model=bert-base-uncased)` → storage-initializer downloads to
/mnt/models → `ModelServer` tokenizes and runs the torch forward on GPU.

The TPU-native flow here: point ``--model-dir`` at the same HF-format
directory a reference user has (config.json + pytorch_model.bin +
vocab.txt). The checkpoint is converted to flax once at load
(models/convert.py), weights live HBM-resident, the forward is the jitted
bucketed path with the Pallas flash-attention kernel, and tokenization is
the real WordPiece over the checkpoint's own vocab.txt — token ids match
the training vocab exactly.

Run:
    python -m kubeflow_tpu.examples.bert_serve --model-dir /mnt/models/bert
    curl -d '{"instances": ["the capital of france is [MASK]."]}' \\
        localhost:8080/v1/models/bert:predict

Without --model-dir it serves a randomly-initialized bert-base (latency-
representative; this env has no egress to fetch real weights).

An InferenceService manifest for the controller path is in
``examples/manifests/bert_isvc.yaml``; `serve.controller.ServeController`
reconciles it into replicas of exactly this server.
"""

from __future__ import annotations

import argparse


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model-dir", type=str, default=None,
                   help="HF-format dir (config.json + pytorch_model.bin + "
                        "vocab.txt) or Orbax checkpoint dir")
    p.add_argument("--name", type=str, default="bert")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--tiny", action="store_true",
                   help="bert-tiny config (CPU-friendly smoke runs)")
    p.add_argument("--interpret", action="store_true",
                   help="Pallas interpret mode (no TPU present)")
    args = p.parse_args(argv)

    import jax

    from kubeflow_tpu.models.bert import bert_base, bert_tiny
    from kubeflow_tpu.serve.runtimes import BertRuntimeModel
    from kubeflow_tpu.serve.server import ModelServer

    cfg = None
    if args.tiny:
        cfg = bert_tiny()
    elif args.model_dir is None:
        cfg = bert_base()
    # else: config comes from the model dir's config.json

    # Compiled Pallas kernels need a TPU. Nothing is swapped from the
    # backend: without one, ask for interpret mode explicitly.
    overrides = {"interpret_kernels": True} if args.interpret else {}

    model = BertRuntimeModel(
        args.name, args.model_dir, config=cfg, **overrides
    )
    model.load()  # fail-closed: a corrupt --model-dir dies HERE, not mid-request

    server = ModelServer(http_port=args.port)
    server.register(model)
    print(f"serving {args.name!r} on :{args.port} "
          f"(backend={jax.default_backend()}, "
          f"tokenizer={type(model.tokenizer).__name__})")
    server.start()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
