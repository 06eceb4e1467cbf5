#!/usr/bin/env python3
"""Prove on demand that the trainer and the serving engine run on the chip.

Run from the root of the checkout on a machine with one TPU v5e:

    python chip_smoke.py            # job, train, serve, kinds, restart phases
    python chip_smoke.py --chips 4  # only the sharded path + its reference

Every phase goes through the entry points a user calls (``LocalCluster``,
``Trainer.fit``, ``ModelServer`` over HTTP, ``InferenceGateway``,
``LMEngine``) at published widths with seeded random weights, checks what
comes out with the repo's own oracles, and prints one JSON object per
phase. The last stdout line is the contract's

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Exit is 0 only if every phase passed: nothing is caught and carried past.
Without a TPU it fails and names the platform JAX found. There is no
option that shrinks it — ``tests/test_chip_smoke.py`` drives the same phase
functions at a tiny size by building its own configuration.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import faulthandler
import functools
import gc
import json
import os
import re
import statistics
import sys
import time
import urllib.request
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax

from kubeflow_tpu.core import compcache
from kubeflow_tpu.core.mesh import MeshSpec, build_mesh
from kubeflow_tpu.models.bert import (
    BertConfig,
    BertForMaskedLM,
    bert_base,
    make_mlm_init_fn,
    make_mlm_loss_fn,
)
from kubeflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    paged_flash_read,
)
from kubeflow_tpu.obs import names
from kubeflow_tpu.serve.model import BucketSpec

REPO = Path(__file__).resolve().parent
#: the driver allows 1200 s; past this the script dumps every thread's
#: stack and exits non-zero instead of holding the chip
DEADLINE_S = 1150
KERNEL_MARKER = "tpu_custom_call"
#: a fixed sampled trace context: only traced requests feed the TTFT/TPOT
#: histograms (obs/trace.py)
TRACE_HEADER = {"x-kft-trace": "00-" + "c5" * 16 + "-" + "7e" * 8 + "-01"}


def emit(phase: str, **fields: Any) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def device_report() -> dict[str, Any]:
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def require_tpu(count: int) -> dict[str, Any]:
    """Open the chip in this process, or fail naming what JAX found."""
    dev = device_report()
    if dev["platform"] != "tpu" or dev["count"] != count:
        raise SystemExit(
            f"chip_smoke needs {count} TPU device(s); JAX found platform "
            f"{dev['platform']!r} ({dev['kind']}) x{dev['count']}"
        )
    return dev


def rel_close(a: float, b: float, tol: float) -> bool:
    return abs(a - b) <= tol * max(1.0, abs(b))


# --------------------------------------------------------------------- #
# configuration: main() builds the real one, the CPU test a tiny one
# --------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class JobConfig:
    steps: int = 20
    timeout_s: float = 600.0


@dataclasses.dataclass(frozen=True)
class TrainPhaseConfig:
    bert: BertConfig
    batch: int = 32
    seq: int = 128
    steps: int = 10
    #: (B, H, S, D) of the bare-kernel forward + backward parity
    #: (tests_chip/test_engine_chip.py's S512 shapes)
    kernel_shape: tuple[int, int, int, int] = (4, 8, 512, 64)
    #: bf16: eps is 2^-8 and the loss averages thousands of tokens
    loss_rtol: float = 1e-2


@dataclasses.dataclass(frozen=True)
class ServePhaseConfig:
    bert: BertConfig
    bert_buckets: BucketSpec
    lm: TransformerConfig
    #: prompt lengths sent to the LM; the longest exceeds the largest
    #: prefill bucket, so the engine prefills in ``prefill_chunk`` pieces
    prompt_lens: tuple[int, ...] = (12, 40, 100, 200, 300)
    lm_buckets: BucketSpec = BucketSpec(batch_sizes=(1,), seq_lens=(32, 128))
    prefill_chunk: int = 128
    max_new_tokens: int = 16
    max_batch: int = 8
    max_seq: int = 512
    chunk_steps: int = 8
    page_size: int = 64
    kv_pool_tokens: int = 8 * 512
    #: the kernel / int8 engines replay these prompts with a smaller
    #: program set (one prefill shape, a short chunk) to bound compiles
    parity_prompt_lens: tuple[int, ...] = (40, 200)
    parity_max_seq: int = 256
    parity_chunk_steps: int = 4
    #: a greedy mismatch passes only as a near-tie: the two tokens' oracle
    #: logits within this fraction of the logits' std at that step
    tie_tol: float = 2.0 ** -4
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class ShardedPhaseConfig:
    bert: BertConfig
    lm: TransformerConfig
    mesh: MeshSpec = MeshSpec(fsdp=2, model=2)
    batch: int = 32
    seq: int = 128
    steps: int = 3
    loss_rtol: float = 1e-2
    prompt_lens: tuple[int, ...] = (24, 100)
    prefill_chunk: int = 128
    max_new_tokens: int = 12
    max_seq: int = 256
    chunk_steps: int = 4
    page_size: int = 64
    kv_pool_tokens: int = 4 * 256
    tie_tol: float = 2.0 ** -4
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class KindsPhaseConfig:
    """A model whose layers differ — window and global attention mixed, a
    leading dense layer, then sigmoid-routed experts beside a shared one —
    in the key names of its published configuration
    (`benchmark/families/afmoe.py` reads them; `benchmark/reference/
    afmoe.py` is the oracle)."""

    model: dict[str, Any]
    #: overrides of the program's configuration (the CPU test's kernels
    #: run under the interpreter)
    program: dict[str, Any] = dataclasses.field(default_factory=dict)
    #: past the window, in several prefill pieces
    prompt_lens: tuple[int, ...] = (40, 200, 300)
    prefill_chunk: int = 128
    max_new_tokens: int = 32
    max_batch: int = 4
    max_seq: int = 512
    chunk_steps: int = 8
    page_size: int = 64
    #: the loosest limits a benchmark cell may state (benchmark/check.py):
    #: a term of the mathematics missing reads whole standard deviations
    regret_mean_max: float = 0.05
    argmax_share_min: float = 0.8
    seed: int = 0


# --------------------------------------------------------------------- #
# job: the launcher path — a child owns the chip, the parent stays off it
# --------------------------------------------------------------------- #

def _parent_holds_no_backend() -> bool:
    # jax has no public "is a backend up?" query, and asking
    # jax.devices() would bring one up
    from jax._src import xla_bridge

    return not xla_bridge.backends_are_initialized()


def phase_job(cfg: JobConfig) -> dict[str, Any]:
    from kubeflow_tpu.orchestrator import (
        JobSpec,
        LocalCluster,
        ReplicaSpec,
        TPURequest,
    )
    from kubeflow_tpu.orchestrator.envwire import WiringConfig
    from kubeflow_tpu.orchestrator.resources import Fleet
    from kubeflow_tpu.orchestrator.spec import RestartPolicy

    t0 = time.perf_counter()
    if not _parent_holds_no_backend():
        raise RuntimeError("job phase must run before this process opens JAX")
    job = JobSpec(
        name="chip-smoke-mnist",
        replicas={
            "worker": ReplicaSpec(
                replicas=1,
                command=(
                    sys.executable, "-m", "kubeflow_tpu.examples.mnist",
                    "--steps", str(cfg.steps), "--log-every", "5",
                ),
                env={"PYTHONPATH": str(REPO)},
                restart_policy=RestartPolicy.NEVER,
                tpu=TPURequest(chips=1),
            )
        },
    )
    # the path behind `kft run --platform tpu`
    with LocalCluster(
        fleet=Fleet.single_host(chips=1),
        wiring=WiringConfig(platform="tpu"),
    ) as cluster:
        uid = cluster.submit(job)
        status = cluster.wait(uid, timeout=cfg.timeout_s)
        log = cluster.logs(uid, "worker", 0)
    if status.phase != "Succeeded":
        raise RuntimeError(f"job ended {status.phase}; worker log:\n{log}")
    m = re.search(r"global (\w+) devices \(device_kind=([^)]*)\)", log)
    if m is None:
        raise RuntimeError(f"worker did not report its platform:\n{log}")
    platform, kind = m.groups()
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU; the job's worker ran on platform "
            f"{platform!r} ({kind})"
        )
    final = re.search(r"final_loss=(\S+)", log)
    if final is None or not np.isfinite(float(final.group(1))):
        raise RuntimeError(f"worker reported no finite final loss:\n{log}")
    if not _parent_holds_no_backend():
        raise RuntimeError("the launcher path opened JAX in the parent")
    return {
        "job": job.name, "condition": status.phase, "steps": cfg.steps,
        "worker_platform": platform, "worker_device_kind": kind,
        "final_loss": float(final.group(1)),
        "parent_opened_backend": False,
        "wall_s": round(time.perf_counter() - t0, 2),
    }


# --------------------------------------------------------------------- #
# train: BERT MLM through Trainer.fit, flash attention compiled
# --------------------------------------------------------------------- #

def _bert_trainer(bert: BertConfig, *, batch, seq, steps, mesh, spec_fn=None):
    from kubeflow_tpu.train.loop import TrainConfig, Trainer

    model = BertForMaskedLM(bert)
    return Trainer(
        init_params=make_mlm_init_fn(model, seq, batch),
        loss_fn=make_mlm_loss_fn(model),
        optimizer=optax.adamw(1e-4),
        config=TrainConfig(
            mesh=mesh, global_batch=batch, steps=steps, log_every=1
        ),
        param_spec_fn=spec_fn,
    )


def _token_batches(vocab_size: int, seq: int, batch: int):
    from kubeflow_tpu.data.synthetic import (
        TokenLMDataset,
        local_shard_iterator,
    )

    ds = TokenLMDataset(vocab_size=vocab_size, seq_len=seq)
    return lambda start: local_shard_iterator(ds, batch, start_step=start)


def _flash_kernel_parity(shape, *, interpret: bool) -> dict[str, float]:
    """Forward + backward of the bare kernel against reference_attention,
    bf16 operands, causal — tests_chip/test_engine_chip.py's check."""
    from kubeflow_tpu.ops.flash_attention import (
        flash_attention,
        reference_attention,
    )

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(r, shape, jnp.bfloat16) for r in (kq, kk, kv))

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) ** 2
        )

    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, interpret=interpret
    )
    ref = lambda q, k, v: reference_attention(q, k, v, causal=True)  # noqa: E731
    out = np.asarray(jax.jit(flash)(q, k, v), np.float32)
    want = np.asarray(jax.jit(ref)(q, k, v), np.float32)
    np.testing.assert_allclose(out, want, atol=2e-2, rtol=2e-2)
    g_flash = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
    worst = 0.0
    for a, b in zip(g_flash, g_ref):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, atol=5e-1, rtol=5e-2)
        worst = max(worst, float(np.max(np.abs(a - b))))
    return {
        "fwd_max_abs_err": float(np.max(np.abs(out - want))),
        "bwd_max_abs_err": worst,
    }


def phase_train(cfg: TrainPhaseConfig) -> dict[str, Any]:
    t0 = time.perf_counter()
    trainer = _bert_trainer(
        cfg.bert, batch=cfg.batch, seq=cfg.seq, steps=cfg.steps,
        mesh=MeshSpec.data_parallel(jax.device_count()),
    )
    data = _token_batches(cfg.bert.vocab_size, cfg.seq, cfg.batch)
    _state, history = trainer.fit(data)
    losses = [h["loss"] for h in history]
    if len(losses) != cfg.steps or not all(np.isfinite(losses)):
        raise RuntimeError(f"expected {cfg.steps} finite losses: {losses}")

    # step-0 loss again, same params / batch / rng, reference attention:
    # init is a pure function of the seed, so this is what fit started from
    state0 = trainer.init_state()
    batch0 = trainer.global_batch_array(next(iter(data(0))))
    rng0 = jax.random.fold_in(state0.rng, 0)
    ref_model = BertForMaskedLM(
        dataclasses.replace(cfg.bert, attn_impl="reference")
    )
    ref_loss = float(
        jax.jit(make_mlm_loss_fn(ref_model))(state0.params, batch0, rng0)[0]
    )
    if not rel_close(losses[0], ref_loss, cfg.loss_rtol):
        raise RuntimeError(
            f"step-0 loss {losses[0]} vs reference attention {ref_loss}"
        )
    expects = cfg.bert.attn_impl == "flash" and not cfg.bert.interpret_kernels
    with jax.set_mesh(trainer.mesh):
        lowered = trainer._build_step(state0).lower(state0, batch0).as_text()
    if expects and KERNEL_MARKER not in lowered:
        raise RuntimeError("the lowered train step holds no Pallas kernel")
    del state0, lowered
    steady = [h["device_step_ms"] for h in history[2:] if "device_step_ms" in h]
    kernel = _flash_kernel_parity(
        cfg.kernel_shape, interpret=cfg.bert.interpret_kernels
    )
    mem = jax.devices()[0].memory_stats() or {}
    return {
        "model": f"bert L{cfg.bert.num_layers} H{cfg.bert.hidden_size}",
        "batch": cfg.batch, "seq": cfg.seq, "steps": cfg.steps,
        "attn_impl": cfg.bert.attn_impl, "kernel_in_step": expects,
        "losses": [round(x, 5) for x in losses],
        "step0_loss_reference_attention": round(ref_loss, 5),
        "compile_s": round(history[0]["compile_ms"] / 1e3, 2),
        "steady_step_ms_median": statistics.median(steady),
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        f"flash_S{cfg.kernel_shape[2]}_parity": kernel,
        "wall_s": round(time.perf_counter() - t0, 2),
    }


# --------------------------------------------------------------------- #
# serve: ModelServer over HTTP (BERT + paged LM engine), gateway, and the
# kernel / int8 read paths against the gather engines
# --------------------------------------------------------------------- #

def _prompts(lens, vocab: int, seed: int) -> list[list[int]]:
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(2, vocab, size=n)] for n in lens]


def _post(url: str, body: dict, headers: dict | None = None) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(req, timeout=900) as r:
        return json.loads(r.read().decode())


def _stream(url: str, body: dict, headers: dict | None = None) -> list[int]:
    """One SSE generate_stream: every frame's tokens, in order."""
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(req, timeout=900) as r:
        text = r.read().decode()
    frames = [
        json.loads(ln[6:]) for ln in text.splitlines()
        if ln.startswith("data: ")
    ]
    bad = [f for f in frames if "error" in f]
    if bad or not frames or not frames[-1].get("done"):
        raise RuntimeError(f"stream did not end cleanly: {frames[-3:]}")
    return [t for f in frames for t in f.get("token_ids", [])]


def _scrape(url: str) -> dict[str, float]:
    """``/metrics`` → {sample name with labels: value}."""
    with urllib.request.urlopen(url + "/metrics", timeout=60) as r:
        text = r.read().decode()
    out = {}
    for ln in text.splitlines():
        if ln and not ln.startswith("#"):
            key, _, val = ln.rpartition(" ")
            out[key] = float(val)
    return out


def _port_of(server) -> int:
    (site,) = server._runner.sites
    return site._server.sockets[0].getsockname()[1]


@functools.lru_cache(maxsize=None)
def _oracle(lm: TransformerConfig):
    """Whole-sequence forward under reference attention, jitted once."""
    model = TransformerLM(dataclasses.replace(lm, attn_impl="reference"))
    return jax.jit(lambda p, t: model.apply({"params": p}, t))


def explain_mismatch(
    lm: TransformerConfig, params, prompt, got, want, *, pad_to: int
) -> dict[str, float]:
    """Where two greedy streams first differ, and how close the oracle
    (reference attention, whole-sequence forward) holds the two tokens."""
    step = next(
        (i for i, (a, b) in enumerate(zip(got, want)) if a != b),
        min(len(got), len(want)),
    )
    if step >= min(len(got), len(want)):
        raise RuntimeError(f"streams differ in length: {got} vs {want}")
    ctx = list(prompt) + list(want[:step])
    tokens = np.zeros((1, pad_to), np.int32)
    tokens[0, : len(ctx)] = ctx
    logits = np.asarray(_oracle(lm)(params, tokens))[
        0, len(ctx) - 1
    ].astype(np.float64)
    top2 = np.sort(logits)[-2:]
    return {
        "step": step, "got": got[step], "want": want[step],
        "top2_gap": float(top2[1] - top2[0]),
        "pair_gap": float(abs(logits[got[step]] - logits[want[step]])),
        "logit_std": float(logits.std()),
    }


def compare_streams(
    label, lm, params, prompts, got, want, *, tie_tol, pad_to
) -> dict[str, Any]:
    """Equal greedy tokens, or a printed near-tie at the first difference."""
    near_ties = []
    for prompt, g, w in zip(prompts, got, want):
        if g == w:
            continue
        why = explain_mismatch(lm, params, prompt, g, w, pad_to=pad_to)
        emit("near_tie", engines=label, prompt_len=len(prompt), **why)
        if why["pair_gap"] > tie_tol * why["logit_std"]:
            raise RuntimeError(f"{label}: tokens differ beyond a tie: {why}")
        near_ties.append(why["step"])
    return {
        "requests": len(prompts),
        "identical": len(prompts) - len(near_ties),
        "near_tie_first_steps": near_ties,
    }


def _engine_answers(engine, prompts, max_new_tokens) -> list[list[int]]:
    return [engine.submit(p, max_new_tokens=max_new_tokens) for p in prompts]


async def _serve(cfg: ServePhaseConfig) -> dict[str, Any]:
    from kubeflow_tpu.gateway.router import ServiceRoute
    from kubeflow_tpu.gateway.server import GatewayConfig, InferenceGateway
    from kubeflow_tpu.serve.engine import LMEngine, LMEngineConfig, LMEngineModel
    from kubeflow_tpu.serve.runtimes import BertRuntimeModel
    from kubeflow_tpu.serve.server import ModelServer

    t0 = time.perf_counter()
    loop = asyncio.get_running_loop()

    def off_loop(fn, *args):
        # blocking HTTP from the loop's own thread would stall the servers
        return loop.run_in_executor(None, fn, *args)

    bert = BertRuntimeModel(
        "bert", None, config=cfg.bert, buckets=cfg.bert_buckets
    )
    lm = LMEngineModel(
        "lm", None, config=cfg.lm, buckets=cfg.lm_buckets,
        max_new_tokens=cfg.max_new_tokens, eos_id=cfg.lm.vocab_size + 1,
        max_batch=cfg.max_batch, max_seq=cfg.max_seq,
        chunk_steps=cfg.chunk_steps, prefill_chunk=cfg.prefill_chunk,
        kv_pool_tokens=cfg.kv_pool_tokens, page_size=cfg.page_size,
    )
    server = ModelServer([bert, lm], http_port=0)  # loads both models
    gateway = None
    parity_engines: list[LMEngine] = []
    try:
        await off_loop(bert.warmup)
        await off_loop(lm.warmup)
        await server.start_async()
        url = f"http://127.0.0.1:{_port_of(server)}"
        gateway = InferenceGateway(
            GatewayConfig(
                probe_interval_s=0.25, upstream_timeout_s=900.0,
                routes=[ServiceRoute(name="lm")],
                backends=[("lm", url, "default")],
            ),
            http_port=0,
        )
        await gateway.start_async()
        gw_url = f"http://127.0.0.1:{gateway.http_port}"
        # ---- BERT: /v2 infer, checked against reference attention ----- #
        seq = cfg.bert_buckets.seq_lens[-1]
        ids = np.asarray(
            _prompts([seq] * 3, cfg.bert.vocab_size, cfg.seed), np.int32
        )
        bert_answers = []
        for n in (1, 2, 1):
            body = {"inputs": [{
                "name": "input_ids", "shape": [n, seq], "datatype": "INT32",
                "data": ids[:n].reshape(-1).tolist(),
            }]}
            out = (await off_loop(
                _post, f"{url}/v2/models/bert/infer", body
            ))["outputs"][0]
            bert_answers.append(np.asarray(out["data"]).reshape(out["shape"]))
        ref = BertForMaskedLM(
            dataclasses.replace(cfg.bert, attn_impl="reference")
        )
        want = np.asarray(jnp.argmax(
            jax.jit(lambda p, t: ref.apply({"params": p}, t))(
                bert._params, ids[:2]
            ), -1,
        ))
        agree = float(np.mean(bert_answers[1] == want))
        if bert_answers[1].shape != (2, seq) or agree < 0.9:
            raise RuntimeError(
                f"bert /infer agrees with reference attention on {agree:.3f} "
                "of positions"
            )
        if not np.array_equal(bert_answers[0], bert_answers[2]):
            raise RuntimeError("bert /infer is not deterministic")

        # ---- LM: concurrent generates, SSE, gateway — twice, so the ---- #
        # second pass runs on warm shapes and may compile nothing
        prompts = _prompts(cfg.prompt_lens, cfg.lm.vocab_size, cfg.seed)
        gen_url = f"{url}/v2/models/lm/generate"

        def generate(p):
            return _post(
                gen_url,
                {"input_ids": p, "max_new_tokens": cfg.max_new_tokens},
                TRACE_HEADER,
            )["token_ids"]

        async def traffic():
            together = await asyncio.gather(
                *[off_loop(generate, p) for p in prompts]
            )
            alone = [await off_loop(generate, p) for p in prompts]
            body = {"input_ids": prompts[1], "max_new_tokens": cfg.max_new_tokens}
            sse = await off_loop(
                _stream, f"{url}/v2/models/lm/generate_stream", body,
                TRACE_HEADER,
            )
            via_gw = await off_loop(
                _stream, f"{gw_url}/v2/models/lm/generate_stream", body
            )
            return list(together), alone, sse, via_gw

        await traffic()  # warm every shape
        warm = compcache.compile_stats()
        together, alone, sse, via_gw = await traffic()
        steady_compiles = int(
            compcache.compile_stats()["programs"] - warm["programs"]
        )
        if steady_compiles:
            raise RuntimeError(
                f"{steady_compiles} programs compiled on warm shapes"
            )
        for toks in together + alone + [sse, via_gw]:
            if len(toks) != cfg.max_new_tokens or not all(
                0 <= t < cfg.lm.vocab_size for t in toks
            ):
                raise RuntimeError(f"bad completion: {toks}")
        if sse != alone[1] or via_gw != alone[1]:
            raise RuntimeError(
                f"stream {sse} / gateway {via_gw} != generate {alone[1]}"
            )
        batching = compare_streams(
            "batched-vs-alone", cfg.lm, lm._params, prompts, together, alone,
            tie_tol=cfg.tie_tol, pad_to=cfg.max_seq,
        )
        metrics = await off_loop(_scrape, url)
        label = '{model="lm"}'
        served = {
            "prefill_pieces": metrics[f"{names.ENGINE_PREFIX}prefill_pieces{label}"],
            "prefill_pieces_flash_read": metrics[
                f"{names.ENGINE_PREFIX}prefill_pieces_flash_read{label}"
            ],
            "decode_chunks": metrics[f"{names.ENGINE_PREFIX}chunks{label}"],
            "carry_uploads": metrics[names.ENGINE_CARRY_UPLOADS_TOTAL + label],
            "ttft_count": metrics[f"{names.SERVER_TTFT_MS}_count{label}"],
            "tpot_count": metrics[f"{names.SERVER_TPOT_MS}_count{label}"],
            "programs_compiled": metrics[names.XLA_PROGRAMS_TOTAL],
            "compile_s": round(metrics[names.XLA_COMPILE_SECONDS_TOTAL], 2),
        }
        if min(served["prefill_pieces"], served["decode_chunks"],
               served["ttft_count"], served["tpot_count"]) <= 0:
            raise RuntimeError(f"/metrics shows no engine work: {served}")
        # the piece's read path is the model's own answer for its shape:
        # on the chip a 128-token piece attends through the flash kernel
        flash_pieces = served["prefill_pieces"] * paged_flash_read(
            cfg.lm, cfg.prefill_chunk
        )
        if served["prefill_pieces_flash_read"] != flash_pieces:
            raise RuntimeError(f"pieces through the flash kernel: {served}")

        # ---- read paths: kernel == gather, int8 kernel == int8 gather -- #
        # the engine chooses the read path itself: the Pallas kernel for
        # the decode step on this backend, the gather under a mesh — here
        # a mesh of one device, which shards nothing
        pprompts = _prompts(
            cfg.parity_prompt_lens, cfg.lm.vocab_size, cfg.seed + 1
        )
        one_device = jax.sharding.Mesh(
            np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model")
        )

        def parity_engine(**kw):
            eng = LMEngine(
                lm._model, cfg.lm, lm._params,
                config=LMEngineConfig(
                    max_batch=cfg.max_batch, max_seq=cfg.parity_max_seq,
                    chunk_steps=cfg.parity_chunk_steps,
                    prefill_buckets=(cfg.prefill_chunk,),
                    prefill_chunk=cfg.prefill_chunk,
                    eos_id=cfg.lm.vocab_size + 1,
                    kv_pool_tokens=cfg.max_batch * cfg.parity_max_seq,
                    page_size=cfg.page_size, **kw,
                ),
            ).start()
            parity_engines.append(eng)
            return eng

        answers = {}
        for name, kw in (
            ("kernel", dict()),
            ("gather", dict(mesh=one_device)),
            ("kernel_int8", dict(kv_quant="int8")),
            ("gather_int8", dict(mesh=one_device, kv_quant="int8")),
        ):
            eng = parity_engine(**kw)
            if eng.kernel_read != name.startswith("kernel"):
                raise RuntimeError(
                    f"{name}: the engine chose kernel_read={eng.kernel_read}"
                )
            answers[name] = await off_loop(
                _engine_answers, eng, pprompts, cfg.max_new_tokens,
            )
        cmp = dict(
            lm=cfg.lm, params=lm._params, prompts=pprompts,
            tie_tol=cfg.tie_tol, pad_to=cfg.max_seq,
        )
        read_paths = {
            "kernel_vs_gather": compare_streams(
                "kernel-vs-gather", got=answers["kernel"],
                want=answers["gather"], **cmp
            ),
            "int8_kernel_vs_int8_gather": compare_streams(
                "int8-kernel-vs-int8-gather", got=answers["kernel_int8"],
                want=answers["gather_int8"], **cmp
            ),
        }
    finally:
        for eng in parity_engines:
            eng.stop()
        if gateway is not None:
            await gateway.stop_async()
        await server.stop_async()
        lm.unload()
        bert.unload()
    return {
        "bert": {
            "model": f"bert L{cfg.bert.num_layers} H{cfg.bert.hidden_size}",
            "infer_requests": len(bert_answers),
            "agreement_with_reference_attention": round(agree, 4),
        },
        "lm": {
            "model": f"decoder L{cfg.lm.n_layers} d{cfg.lm.d_model} "
                     f"h{cfg.lm.n_heads} ff{cfg.lm.d_ff} v{cfg.lm.vocab_size}",
            "prompt_lens": list(cfg.prompt_lens),
            "prefill_chunk": cfg.prefill_chunk,
            "generate_requests_per_pass": 2 * len(prompts), "passes": 2,
            "sse_stream_tokens": len(sse), "gateway_stream_tokens": len(via_gw),
            "batched_vs_alone": batching,
            "compiles_on_warm_shapes": steady_compiles,
        },
        "metrics": served,
        "read_paths": read_paths,
        "wall_s": round(time.perf_counter() - t0, 2),
    }


def phase_serve(cfg: ServePhaseConfig) -> dict[str, Any]:
    return asyncio.run(_serve(cfg))


# --------------------------------------------------------------------- #
# kinds: layers that differ, routed experts — the engine against the
# plain reference
# --------------------------------------------------------------------- #

def phase_kinds(cfg: KindsPhaseConfig) -> dict[str, Any]:
    """`LMEngine` serving a model of mixed layer kinds with dropless routed
    experts, compiled: prefill in pieces, decode through the paged kernel
    (each layer passing its own window) and the grouped product; every
    served token is rated by the float32 reference's full forward pass —
    how far its logit lies below the position's best, in standard
    deviations of the position's logits."""
    from benchmark.families import afmoe
    from benchmark.weights import seeded_params
    from kubeflow_tpu.ops.grouped_matmul import gmm_kernel_runs
    from kubeflow_tpu.serve.engine import LMEngine, LMEngineConfig

    t0 = time.perf_counter()
    pc = afmoe.program_config(cfg.model, **cfg.program)
    model = TransformerLM(pc)
    params = seeded_params(afmoe.abstract_params(model), cfg.seed, pc.dtype)
    engine = LMEngine(model, pc, params, config=LMEngineConfig(
        max_batch=cfg.max_batch, max_seq=cfg.max_seq, chunk_steps=cfg.chunk_steps,
        prefill_buckets=(cfg.prefill_chunk,), prefill_chunk=cfg.prefill_chunk,
        eos_id=pc.vocab_size + 1, page_size=cfg.page_size,
    )).start()
    prompts = _prompts(cfg.prompt_lens, pc.vocab_size, cfg.seed)
    try:
        answers = _engine_answers(engine, prompts, cfg.max_new_tokens)
    finally:
        engine.stop()
    stats = engine.stats
    if not engine.kernel_read or not gmm_kernel_runs(pc.interpret_kernels):
        raise RuntimeError("the engine did not take the kernels' read path")
    regrets = []
    for prompt, served in zip(prompts, answers):
        seq = np.zeros((cfg.max_seq,), np.int32)
        seq[: len(prompt) + len(served)] = prompt + served
        rows = np.arange(len(prompt) - 1, len(prompt) + len(served) - 1)
        logits = np.asarray(
            afmoe.reference_logits(params, seq, rows, cfg.model), np.float64
        )
        chosen = logits[np.arange(len(rows)), np.asarray(served)]
        regrets.append((logits.max(axis=1) - chosen) / logits.std(axis=1))
    regrets = np.concatenate(regrets)
    out = {
        "model": f"{pc.n_layers} layers, windows {[k.window for k in pc.kinds]}, "
                 f"{pc.moe.num_experts} experts top-{pc.moe.top_k}",
        "tokens_rated": int(regrets.size),
        "regret_mean": float(regrets.mean()), "regret_max": float(regrets.max()),
        "argmax_share": float((regrets == 0).mean()),
        "moe_assignments_decode": int(stats["moe_assignments_decode"]),
        "moe_experts_touched_decode": int(stats["moe_experts_touched_decode"]),
        "kv_pages_dead_window": int(stats["kv_pages_dead_window"]),
        "kv_pages_held": int(stats["kv_pages_held"]),
        "wall_s": round(time.perf_counter() - t0, 2),
    }
    if (out["regret_mean"] > cfg.regret_mean_max
            or out["argmax_share"] < cfg.argmax_share_min
            or any(len(a) != cfg.max_new_tokens for a in answers)):
        raise RuntimeError(f"the engine is not the reference's model: {out}")
    return out


# --------------------------------------------------------------------- #
# restart: what the compile cache holds for the next start
# --------------------------------------------------------------------- #

def _cache_entries(cache_dir: str | None) -> int:
    if cache_dir is None or not os.path.isdir(cache_dir):
        return 0
    return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))


def phase_restart(cache_dir: str | None, entries_at_start: int) -> dict[str, Any]:
    stats = compcache.compile_stats()
    return {
        "cache_dir": cache_dir,
        "entries_at_start": entries_at_start,
        "entries_written": _cache_entries(cache_dir) - entries_at_start,
        "warm_start": entries_at_start > 0,
        # this process (train + serve); the job's worker counted its own
        "programs_built": int(stats["programs"]),
        "programs_from_cache": int(stats["cache_hits"]),
        "compile_s": round(stats["seconds"], 2),
    }


# --------------------------------------------------------------------- #
# --chips 4: the sharded trainer and the tensor-parallel engine, each
# against one device of the same process
# --------------------------------------------------------------------- #

def bytes_in_use() -> list[int | None]:
    """Per-device allocator reading (None on the CPU backend), with
    unreachable arrays of earlier phases collected first."""
    gc.collect()
    return [(d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()]


def placement_report(tree, n_devices: int, before) -> dict[str, Any]:
    """Is the sharding real? Every sharded leaf on all devices, each
    holding ~1/n of the sharded bytes, and the allocator agreeing: what
    each device gained since ``before`` is non-zero and roughly equal."""
    sharded = [
        x for x in jax.tree_util.tree_leaves(tree)
        if isinstance(x, jax.Array) and not x.sharding.is_fully_replicated
    ]
    if not sharded:
        raise RuntimeError("no sharded leaf: everything is replicated")
    for x in sharded:
        if len(x.sharding.device_set) != n_devices:
            raise RuntimeError(
                f"leaf {x.shape} lives on {len(x.sharding.device_set)} "
                f"devices, not {n_devices}"
            )
    total = sum(x.nbytes for x in sharded)
    per_device: dict[int, int] = {}
    for x in sharded:
        for s in x.addressable_shards:
            per_device[s.device.id] = per_device.get(s.device.id, 0) + s.data.nbytes
    shares = {d: b / total for d, b in sorted(per_device.items())}
    # leaves split on one mesh axis of two hold 1/2, so allow some above 1/n
    if len(shares) != n_devices or not all(
        0.9 / n_devices <= s <= 1.5 / n_devices for s in shares.values()
    ):
        raise RuntimeError(f"per-device share of sharded bytes: {shares}")
    gained = None
    if all(b is not None for b in before):  # the CPU backend reports none
        gained = [a - b for a, b in zip(bytes_in_use(), before)]
        if min(gained) <= 0 or max(gained) > 1.5 * min(gained):
            raise RuntimeError(f"bytes_in_use gained per device: {gained}")
    return {
        "devices": sorted(per_device),
        "sharded_leaves": len(sharded), "sharded_bytes": total,
        "share_per_device": [round(s, 4) for s in shares.values()],
        "bytes_in_use_gained": gained,
    }


def _sharded_train(cfg: ShardedPhaseConfig) -> dict[str, Any]:
    from kubeflow_tpu.parallel.sharding import transformer_rules

    trainer = _bert_trainer(
        cfg.bert, batch=cfg.batch, seq=cfg.seq, steps=cfg.steps,
        mesh=cfg.mesh, spec_fn=transformer_rules(),
    )
    data = _token_batches(cfg.bert.vocab_size, cfg.seq, cfg.batch)
    before = bytes_in_use()
    state, history = trainer.fit(data)
    losses = [h["loss"] for h in history]
    placement = placement_report(state.params, cfg.mesh.total_devices, before)
    del state

    # the same loss and update, jitted plainly on one device
    one = jax.devices()[0]
    loss_fn, tx = trainer.loss_fn, trainer.optimizer
    state0 = trainer.init_state()
    params = jax.device_put(jax.device_get(state0.params), one)
    rng = jax.device_put(jax.device_get(state0.rng), one)
    del state0
    opt_state = jax.jit(tx.init)(params)

    @jax.jit
    def step(params, opt_state, batch, rng):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, rng
        )
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    it = iter(data(0))
    plain = []
    for i in range(cfg.steps):
        batch = jax.device_put(next(it), one)
        params, opt_state, loss = step(
            params, opt_state, batch, jax.random.fold_in(rng, i)
        )
        plain.append(float(loss))
    if len(losses) != cfg.steps or not all(
        rel_close(a, b, cfg.loss_rtol) for a, b in zip(losses, plain)
    ):
        raise RuntimeError(f"sharded losses {losses} vs one device {plain}")
    return {
        "mesh": {k: v for k, v in cfg.mesh.to_dict().items() if v > 1},
        "losses_sharded": [round(x, 5) for x in losses],
        "losses_one_device": [round(x, 5) for x in plain],
        "placement": placement,
    }


def _tensor_parallel_engine(cfg: ShardedPhaseConfig) -> dict[str, Any]:
    from kubeflow_tpu.serve.engine import LMEngine, LMEngineConfig

    n = cfg.mesh.total_devices
    model = TransformerLM(cfg.lm)
    # held on the host, so no device starts out with the whole model
    params = jax.device_get(jax.jit(
        lambda: model.init(
            jax.random.PRNGKey(cfg.seed), jnp.zeros((1, 8), jnp.int32)
        )["params"]
    )())
    prompts = _prompts(cfg.prompt_lens, cfg.lm.vocab_size, cfg.seed)
    base = dict(
        max_batch=4, max_seq=cfg.max_seq, chunk_steps=cfg.chunk_steps,
        prefill_buckets=(cfg.prefill_chunk,), prefill_chunk=cfg.prefill_chunk,
        eos_id=cfg.lm.vocab_size + 1, kv_pool_tokens=cfg.kv_pool_tokens,
        page_size=cfg.page_size,
    )
    def answers_of(mesh, inspect):
        before = bytes_in_use()
        eng = LMEngine(
            model, cfg.lm, params, config=LMEngineConfig(mesh=mesh, **base)
        ).start()
        try:
            seen = inspect(eng, before)
            return _engine_answers(eng, prompts, cfg.max_new_tokens), seen
        finally:
            eng.stop()

    tp, placement = answers_of(
        build_mesh(MeshSpec(model=n)),
        lambda eng, before: {
            "params": placement_report(eng.params, n, before),
            "kv_pool": placement_report(eng.cache, n, before),
            "kernel_read": eng.kernel_read,
        },
    )
    if placement.pop("kernel_read"):
        raise RuntimeError(
            "an engine under a mesh chose the Pallas read path, which is "
            "not partitioned"
        )
    # no mesh on a multi-device host: everything on the default device —
    # by design, one replica per chip
    one_device, where = answers_of(
        None,
        lambda eng, _before: sorted({
            d.id for x in jax.tree_util.tree_leaves(eng.params)
            for d in x.sharding.device_set
        }),
    )
    tokens = compare_streams(
        "tp-vs-one-device", cfg.lm, params, prompts, tp, one_device,
        tie_tol=cfg.tie_tol, pad_to=cfg.max_seq,
    )
    return {
        "mesh": {"model": n}, "read_path": "gather",  # checked above
        "tokens": tokens, "placement": placement,
        "engine_without_mesh_uses_devices": where,
    }


def phase_sharded(cfg: ShardedPhaseConfig) -> dict[str, Any]:
    t0 = time.perf_counter()
    return {
        "train": _sharded_train(cfg),
        "engine": _tensor_parallel_engine(cfg),
        "wall_s": round(time.perf_counter() - t0, 2),
    }


# --------------------------------------------------------------------- #
# the real configuration, and nothing else
# --------------------------------------------------------------------- #

def lm_config() -> TransformerConfig:
    """The decoder bench.py calls its on-chip model."""
    return TransformerConfig(
        vocab_size=32768, d_model=1024, n_layers=12, n_heads=16, d_ff=4096,
        max_seq_len=2048, causal=True, dtype=jnp.bfloat16,
        attn_impl="flash", interpret_kernels=False,
    )


def bert_config() -> BertConfig:
    """bert-base-uncased's published widths."""
    return bert_base(
        dtype=jnp.bfloat16, attn_impl="flash", interpret_kernels=False
    )


def kinds_config() -> dict[str, Any]:
    """Trinity-Mini's pattern at a smoke's size: head width 128 (not
    hidden / heads), window 128, one dense layer then sliding, sliding,
    sliding, full expert layers of 16 experts top-4 and a shared one."""
    return dict(
        family="afmoe", hidden_size=512, num_attention_heads=8,
        num_key_value_heads=2, head_dim=128,
        layer_types=["sliding_attention"] * 4 + ["full_attention"],
        sliding_window=128, rope_theta=10000, rms_norm_eps=1e-5,
        num_dense_layers=1, intermediate_size=1024, num_hidden_layers=5,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=256,
        num_shared_experts=1, score_func="sigmoid", route_norm=True,
        route_scale=2.826, mup_enabled=True, vocab_size=4096,
        tie_word_embeddings=False, hidden_act="silu",
        max_position_embeddings=1024, activation_dtype="bfloat16",
        weight_dtype="bfloat16",
    )


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the sharded trainer / tensor-parallel engine "
             "and what they are compared with",
    )
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(
        DEADLINE_S, exit=True, file=sys.__stderr__
    )

    if args.chips == 4:
        device = require_tpu(4)
        compcache.enable_compilation_cache()
        emit("sharded", **phase_sharded(
            ShardedPhaseConfig(bert=bert_config(), lm=lm_config())
        ))
    else:
        cache_dir = compcache.enable_compilation_cache()  # opens no backend
        entries_at_start = _cache_entries(cache_dir)
        emit("job", **phase_job(JobConfig()))
        device = require_tpu(1)
        emit("train", **phase_train(TrainPhaseConfig(bert=bert_config())))
        emit("serve", **phase_serve(ServePhaseConfig(
            bert=bert_config(),
            bert_buckets=BucketSpec(batch_sizes=(1, 4), seq_lens=(128,)),
            lm=lm_config(),
        )))
        emit("kinds", **phase_kinds(KindsPhaseConfig(model=kinds_config())))
        emit("restart", **phase_restart(cache_dir, entries_at_start))
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
