"""Continuous-batching LM engine — the vLLM-scheduler analog, TPU-style.

Reference analog: the KServe HuggingFace runtime's vLLM backend ([kserve]
python/huggingfaceserver — UNVERIFIED, mount empty, SURVEY.md §0), whose
core idea is continuous batching: requests join and leave a RUNNING decode
batch, so short completions never wait for long ones and the accelerator
never decodes dead rows.

TPU-first shape of the same idea (no per-token host hops, no dynamic
shapes):

- **One persistent KV cache**, a paged pool (serve/paging.py: the vLLM
  block-table analog), lives in HBM. A request is admitted by claiming
  a FREE ROW and the pages of its worst case (prompt + max_new_tokens)
  and prefilling through the row's block table; a row's tokens are
  contiguous, so position == token index everywhere.
- **Decode runs in fixed-size chunks**: one jitted ``lax.scan`` of
  ``chunk_steps`` decode steps for ALL rows (inactive rows are masked and
  emit pads). The host syncs once per chunk — admission, completion, and
  row recycling happen at chunk boundaries. ``chunk_steps`` trades
  admission latency against host-sync overhead.
- **Static shapes everywhere**: prompts pad to prefill buckets; the chunk
  program is compiled once per (max_batch, chunk) — admission never
  recompiles anything.
- **Pipelined decode** (``pipeline_depth=1``, the default): the decode
  steady state performs ZERO per-chunk host round-trips. The per-row
  scheduling arrays (last token, generation counts, activity, budgets,
  temperatures) live on device as a *carry* threaded from one chunk
  dispatch into the next, and chunk N+1 is dispatched *before* chunk N's
  tokens are drained D2H — JAX async dispatch overlaps the host-side
  drain/postprocess of chunk N with chunk N+1's device compute (the same
  gap vLLM's async engine loop closes for GPUs). Admissions, prefill
  completions, cancellations and page reallocation are *epochs*: they
  dirty the carry, and the next chunk's carry is then merged ON THE
  DEVICE — the in-flight chunk's outputs for rows the host left alone,
  the admitted rows' first tokens still as device handles, the host's
  values for every row it edited — after one H2D of the fields only the
  host changes. The epoch's chunk is dispatched before any first token
  is read or the chunk in flight is drained, so an epoch keeps the
  pipeline full; it drains first only when nothing is in flight (the
  batch ran empty) and at the end of a burst. ``pipeline_depth=0`` keeps
  the old fully-synchronous loop selectable for parity testing and
  debugging.

Correctness contract (pinned by tests/test_engine.py): a request's tokens
are IDENTICAL to what the whole-batch ``make_generate_fn`` path produces
for the same prompt under greedy decoding — continuous batching *and* the
pipelined carry are scheduling optimizations, never a numerics change.
The speculative chunk is safe because every per-row liveness decision the
device needs (EOS, budget exhaustion) is already computed in-graph; only
host-initiated transitions (admit/cancel/prefill-activate) require an
epoch, and those are exactly the rows the merge takes from the host.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import queue
import threading
import time
from dataclasses import dataclass, field, replace as _dc_replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.core.parts import CARRY, HEAD, MERGE, MOE_STATS, SAMPLE
from kubeflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    init_paged_kv_cache,
    paged_flash_read,
    paged_kernel_read,
)
from kubeflow_tpu.obs import names, prom
from kubeflow_tpu.obs.headers import (
    PREFILL_PEER_HEADER,
    SESSION_HEADER,
    TRACE_HEADER,
)
from kubeflow_tpu.obs.trace import (
    TRACER,
    ctx_from_headers,
    observe_request_latency,
)
from kubeflow_tpu.ops.flash_tuning import select_paged_geometry
from kubeflow_tpu.ops.paged_attention import paged_work
from kubeflow_tpu.serve.deadline import (
    ADMISSION_SHED,
    DEADLINE_EXPIRED,
    AdmissionShed,
    DeadlineExceeded,
    deadline_from_headers,
    priority_from_headers,
    resume_from_headers,
    seed_from_headers,
)
from kubeflow_tpu.serve.generate import (
    LMRuntimeModel,
    sample_logits as _sample,
)
from kubeflow_tpu.serve.kv_codec import decode_kv_entries
from kubeflow_tpu.serve.kv_tier import HostKVTier
from kubeflow_tpu.serve.paging import PageAllocator

#: idle park bound — every waker (submit, stream-cancel, stop) sets
#: ``_work``, so this timeout is only a belt-and-braces sweep, not a poll
_IDLE_PARK_S = 5.0

#: what the scheduler thread is doing, as ``LMEngine._phase`` names it: on
#: that thread the phases never overlap except by nesting (a single-piece
#: admission prefills inside ``admit``), each counts its SELF time, and with
#: the small unnamed rest they add up to ``stats["sched_loop_s"]``
_SCHED_PHASES = (
    "admit",             # retire cancelled/expired rows, queue poll, pages,
                         # mirrors, prefix store, first-token push
    "prefill_dispatch",  # build one prefill piece and enqueue its program
    "prefill_wait",      # blocked on a final piece's first token — and on
                         # every program queued before that piece
    "carry_upload",      # the epoch's H2D of the host's fields + the merge
    "chunk_dispatch",    # rng split, table widening, enqueue a decode chunk
    "drain_wait",        # blocked on a chunk's results (D2H)
    "drain_emit",        # credit tokens, push, spans, retirements
    "park",              # nothing to do
)
# As the phases above name the scheduler's time, parts name the device's:
# the programs below scope what they compute outside the model by
# core/parts.py's names.

#: disaggregated-serving wire metrics: per-request KV span bytes by leg
#: (``export`` = prefill replica serving :prefill, ``import`` = decode
#: replica pulling) and the end-to-end latency of one ship
KV_SHIP_BYTES = prom.REGISTRY.counter(
    names.ENGINE_KV_SHIP_BYTES_TOTAL,
    "bytes of per-request KV spans shipped between replicas",
    labels=("model", "direction"),
)
KV_SHIP_MS = prom.REGISTRY.histogram(
    names.ENGINE_KV_SHIP_MS,
    "one KV-span ship leg (fetch + decode + validate), milliseconds",
)
#: mid-stream failover (gateway resume contract): requests admitted with
#: a committed-token prefix — the engine half of a transparent migration
RESUME_ADMITS = prom.REGISTRY.counter(
    names.ENGINE_RESUME_ADMITS_TOTAL,
    "requests admitted with a committed-token resume prefix",
    labels=("model",),
)


@dataclass
class LMEngineConfig:
    """Engine tuning knobs, bundled so deployments can pass one object
    (and so the pipeline knob has a named home). Every field can also be
    given directly to ``LMEngine(...)`` as a keyword override.

    ``pipeline_depth``: 1 (default) runs the pipelined decode loop —
    device-resident carry + one-chunk-ahead dispatch; 0 selects the
    fully-synchronous inline loop (per-chunk H2D/D2H) for parity testing
    and debugging. Depths > 1 are rejected: a second speculative chunk
    would decode on a carry the host can no longer merge-edit cheaply,
    for no additional overlap (one chunk already hides the drain).

    ``spec_draft_tokens`` (K): in-graph speculative decoding
    (serve/speculative.py) — each decode step drafts up to K tokens by
    prompt-lookup against the row's own device-resident token history
    and verifies them in ONE (K+1)-position forward, emitting up to K+1
    tokens per forward. 0 (default) disables it: the classic one-token
    step program runs, byte-compatible with the pre-spec engine. Greedy
    decoding is byte-identical either way; K only changes how many
    forwards the same token stream costs. ``spec_ngram``: the match
    window the drafter keys on (>= 1). Speculation needs no KV
    head-room: span positions past a row's budgeted region write to the
    pool's scratch page.

    ``kv_pool_tokens``: the KV cache is ONE paged pool of this many
    tokens, billed per resident token; a request that finds no pages
    waits (FIFO) for completions to free some. ``None`` (default) sizes
    the pool so that every row can hold ``max_seq`` tokens at once —
    ``max_batch × ceil(max_seq / page_size)`` pages beside the
    allocator's scratch page — and admission is then never held back by
    pages. A deployer names a smaller number to pack mixed-length
    traffic into less HBM than that rectangle. Admission is by the
    prompt's own length: ``len(ids) + max_new_tokens <= max_seq``.

    The read path is not a setting: a decode step and a speculative
    verify span read K and V through the Pallas kernel
    (ops/paged_attention.py: only the pages a row holds leave HBM, once,
    online softmax fused) where the backend is a TPU — or
    ``TransformerConfig.interpret_kernels`` asks for the Pallas
    interpreter — and no mesh is in force; there a prefill piece of
    whole 128-token blocks gathers its row's window and attends through
    the flash forward kernel (ops/flash_attention.py: no score array is
    written). An engine under a mesh, a CPU without the interpreter and
    the spans between (a piece of 16 to 127 tokens, or off the block)
    gather the rows' windows and attend in-graph
    (``models/transformer.py::paged_kernel_read``, ``paged_flash_read``).
    Greedy token streams are byte-identical between the decode paths;
    ``stats["decode_chunks_kernel_read"]`` counts the chunks that took
    the kernel, ``stats["prefill_pieces_flash_read"]`` the pieces that
    took the flash kernel.
    ``kv_quant``: ``"none"`` (default, byte-exact with the pre-quant
    engine) or ``"int8"`` — per-(kv_head, token) symmetric int8 pool
    with f32 scale side arrays, quantize-on-write / dequantize-on-read;
    pool bytes per resident token halve vs bf16 (quarter vs f32).
    ``page_size=None`` selects the measured page size from
    ops/flash_tuning.py's table
    (``paged:{head_dim}`` section, written by ``sweep_paged_pages``)."""

    max_batch: int = 8
    max_seq: int = 256
    chunk_steps: int = 8
    prefill_buckets: tuple[int, ...] = (32, 128)
    eos_id: int = 1
    pad_id: int = 0
    seed: int = 0
    max_queue: int = 64
    prefix_cache_entries: int = 0
    prefix_cache_tokens: int | None = None
    prefill_chunk: int | None = None
    mesh: Any = None
    rules: Any = None
    kv_pool_tokens: int | None = None
    page_size: int | None = 64
    pipeline_depth: int = 1
    spec_draft_tokens: int = 0
    spec_ngram: int = 3
    kv_quant: str = "none"
    #: host-RAM KV tier byte budget (serve/kv_tier.py): > 0 enables the
    #: tier — sessioned rows swap their KV span out through the npz codec
    #: on finish and back in (byte-identically) on the session's next
    #: turn. 0 (default) disables it: no offload thread, no host pool.
    host_kv_bytes: int = 0


@dataclass
class _PendingChunk:
    """One dispatched-but-undrained decode chunk: device handles to its
    outputs plus the dispatch-time slot snapshot, so the drain can mask
    out speculative results of rows retired while the chunk was in
    flight (cancellation, re-admission)."""

    toks: Any          # (B, T) device tokens — (B, T, K+1) planes w/ spec
    valid: Any         # (B, T) device validity — (B, T, K+1) w/ spec
    last_tok: Any      # (B,) post-chunk carry token
    gen_count: Any     # (B,) post-chunk generation counts
    active_out: Any    # (B,) post-chunk liveness
    active_in: Any     # (B,) liveness AT DISPATCH (drain credit gate)
    slots: list        # _Request-per-row snapshot at dispatch
    # speculative decoding extras (None when spec_draft_tokens == 0):
    eos: Any = None    # (B, T) a live EOS landed in this step's span
    prop: Any = None   # (B, T) draft tokens proposed (live rows)
    acc: Any = None    # (B, T) draft tokens accepted (live rows)
    #: the chunk's routing counts (LMEngine._moe_counts), device handles;
    #: None for a model with no expert layer
    moe: Any = None
    # dispatch stamp (time.monotonic) — the drain records one
    # ``decode.chunk`` span per traced resident row from this
    t_dispatch: float = 0.0


@dataclass
class _Request:
    ids: list[int]
    max_new_tokens: int
    temperature: float
    done: threading.Event = field(default_factory=threading.Event)
    tokens: list[int] = field(default_factory=list)
    error: Exception | None = None
    # streaming consumers get every appended token incrementally; None for
    # plain submit() (no queue churn on the non-streaming path)
    live: "queue.Queue[list[int] | None] | None" = None
    # consumer walked away (client disconnect): free the row at the next
    # chunk boundary instead of decoding tokens nobody reads
    cancelled: threading.Event = field(default_factory=threading.Event)
    # end-to-end deadline (absolute time.monotonic()): expired requests
    # are retired from the queue before ever costing a decode slot, and
    # mid-decode rows are cancelled at the next epoch boundary
    deadline: float | None = None
    # tenant priority (higher = shed last): under sustained overload the
    # lowest-priority queued request is evicted first
    priority: int = 0
    # disaggregated prefill (prefill-pool side): run ONLY the prefill and
    # hand the finished KV span back instead of activating the row —
    # _advance_prefill's final piece fills kv_span/kv_span_meta and
    # retires the request without ever decoding
    want_kv_span: bool = False
    kv_span: Any = None
    kv_span_meta: dict | None = None
    # disaggregated decode (decode-pool side): a peer-prefilled span
    # (PreparedKVSpan) admitted by implant — this engine never computes a
    # prefill chunk for the request
    kv_inject: "PreparedKVSpan | None" = None
    # host-RAM KV tier (serve/kv_tier.py): session identity — finished
    # rows swap their span out under this key; the session's next turn
    # swaps it back in
    session: str | None = None
    # mid-stream failover resume: how many committed tokens the prompt
    # was extended by (``ids`` already contains them — stats/trace only),
    # and the per-request sampling seed (None = legacy engine-RNG draws;
    # seeded rows draw token t from fold_in(PRNGKey(seed), position_of_t)
    # so a resumed stream continues the exact sampling stream)
    resume: int = 0
    seed: int | None = None
    # set on admission:
    row: int = -1
    # request tracing (obs/trace.py) — only populated for requests whose
    # submit carried a trace context; warmup and untraced callers pay
    # nothing on this path. ``espan`` is the engine-stage span, qspan /
    # pspan its queue.wait / prefill children; all are closed by
    # finish() from whatever terminal state the request reached.
    model: str = "engine"
    espan: Any = None
    qspan: Any = None
    pspan: Any = None
    t_enqueue: float = 0.0
    t_first: float = 0.0
    t_last: float = 0.0

    def push(self, toks: list[int]) -> None:
        if toks and self.t_enqueue:
            # TTFT/TPOT stamps for traced requests: first / latest token
            # arrival at the host (the moment a client could see them)
            self.t_last = time.monotonic()
            if not self.tokens:
                self.t_first = self.t_last
        self.tokens.extend(toks)
        if self.live is not None and toks:
            self.live.put(list(toks))

    def finish(self) -> None:
        self._end_trace()
        if self.live is not None:
            self.live.put(None)  # stream sentinel
        self.done.set()

    def _end_trace(self) -> None:
        """Close the engine-stage spans from the request's terminal state
        and record its TTFT/TPOT. Idempotent (finish can race between the
        enqueue path and the drain): the espan handle is taken once."""
        span, self.espan = self.espan, None
        if span is None:
            return
        err = self.error
        if err is None:
            status = "cancelled" if self.cancelled.is_set() else "ok"
        elif isinstance(err, DeadlineExceeded):
            status = "deadline"
            span.event("deadline_expired", stage=err.stage)
        elif isinstance(err, AdmissionShed):
            status = "shed"
            span.event("admission_shed", reason=err.reason)
        elif type(err).__name__ == "EngineRestarting":
            # watchdog poisoned this engine instance mid-flight; the
            # failure is retryable on a fresh engine / peer replica
            status = "poisoned"
            span.event("watchdog_poisoned", retryable=True)
        else:
            status = "error"
            span.set_attr("error", f"{type(err).__name__}: {err}")
        for sub in (self.qspan, self.pspan):
            if sub is not None:
                sub.end(status if status != "ok" else None)
        self.qspan = self.pspan = None
        n = len(self.tokens)
        span.set_attr("tokens_emitted", n)
        if self.t_first:
            ttft_ms = (self.t_first - self.t_enqueue) * 1e3
            tpot_ms = None
            if n >= 2 and self.t_last > self.t_first:
                tpot_ms = (self.t_last - self.t_first) / (n - 1) * 1e3
            span.set_attr("ttft_ms", round(ttft_ms, 3))
            if tpot_ms is not None:
                span.set_attr("tpot_ms", round(tpot_ms, 3))
            observe_request_latency(
                self.model, ttft_ms=ttft_ms, tpot_ms=tpot_ms
            )
        span.end(status)


@dataclass(frozen=True)
class PreparedKVSpan:
    """One shipped per-request KV span validated against a specific
    engine (``LMEngine.prepare_kv_span``) and device-put, ready for
    ``submit(kv_span=...)``: the per-layer tree (jnp), the ship meta
    (``real_len`` / ``first_tok`` / ``valid``), and the ceil-16 window
    width the tree covers."""

    tree: Any
    meta: dict
    n16: int


class EngineOverloaded(RuntimeError):
    """Admission queue full — callers should shed load (HTTP 429)."""


#: the routing counters a program with expert layers returns beside its
#: tokens (`LMEngine._moe_counts`), each kept per phase in ``stats``
_MOE_COUNTERS = ("assignments", "experts_touched", "layer_steps", "load_max")

#: where the epoch's merge takes a row's carry from (``LMEngine._merge``):
#: the chunk in flight, the host mirrors, or its final prefill piece
_KEEP, _HOST, _FIRST = 0, 1, 2


class LMEngine:
    """Continuous-batching engine over a TransformerLM + params.

    ``submit()`` is thread-safe and blocks until the completion is ready;
    concurrent submitters share decode chunks. Drive it from a thread pool
    (the model-server executor) or a dedicated client thread per request.
    """

    def __init__(
        self,
        model: TransformerLM,
        cfg: TransformerConfig,
        params,
        *,
        config: LMEngineConfig | None = None,
        **overrides,
    ):
        if config is None:
            config = LMEngineConfig()
        if overrides:
            # unknown keys raise TypeError naming the offender — the same
            # contract the old explicit keyword list gave callers
            config = _dc_replace(config, **overrides)
        self.engine_config = config
        max_batch, max_seq = config.max_batch, config.max_seq
        chunk_steps = config.chunk_steps
        prefill_buckets = config.prefill_buckets
        eos_id, pad_id, seed = config.eos_id, config.pad_id, config.seed
        max_queue = config.max_queue
        prefix_cache_entries = config.prefix_cache_entries
        prefix_cache_tokens = config.prefix_cache_tokens
        prefill_chunk = config.prefill_chunk
        mesh, rules = config.mesh, config.rules
        kv_pool_tokens, page_size = config.kv_pool_tokens, config.page_size
        if config.pipeline_depth not in (0, 1):
            raise ValueError(
                "pipeline_depth must be 0 (inline) or 1 (one-chunk-ahead); "
                f"got {config.pipeline_depth}"
            )
        self.pipeline_depth = config.pipeline_depth
        if config.spec_draft_tokens < 0:
            raise ValueError(
                f"spec_draft_tokens must be >= 0 (0 disables speculative "
                f"decoding); got {config.spec_draft_tokens}"
            )
        if config.spec_draft_tokens and config.spec_ngram < 1:
            raise ValueError(
                f"spec_ngram must be >= 1 when speculative decoding is on; "
                f"got {config.spec_ngram}"
            )
        #: speculative decode: K draft tokens verified per forward (0=off)
        self.spec_k = config.spec_draft_tokens
        self.spec_ngram = config.spec_ngram
        if config.kv_quant not in ("none", "int8"):
            raise ValueError(
                f"kv_quant must be 'none' or 'int8'; got {config.kv_quant!r}"
            )
        #: KV pool precision
        self.kv_quant = config.kv_quant
        if page_size is None:
            # measured page size from the on-chip sweep table (falls back
            # to the 64-token default when no table entry exists — the
            # byte-compat default)
            from kubeflow_tpu.ops.flash_tuning import select_paged_page_size

            page_size = select_paged_page_size(cfg.head_dim)
        if not cfg.causal:
            raise ValueError("LMEngine needs a causal TransformerConfig")
        from kubeflow_tpu.core.compcache import enable_compilation_cache

        enable_compilation_cache()  # engine start is compile-dominated
        self.model, self.cfg = model, cfg
        #: whether the model routes to experts: its programs then return
        #: the routing counts of live rows beside their tokens
        self._moe = cfg.moe_layers > 0
        #: assignments per expert, live rows, since the engine started
        self.moe_expert_load = np.zeros(
            (cfg.moe.num_experts if self._moe else 0,), np.int64
        )
        self.mesh = mesh
        #: label for engine-stage spans and the TTFT/TPOT histograms;
        #: LMEngineModel stamps its serving-model name here
        self.model_name = "engine"
        self.page_size = page_size
        if mesh is not None:
            # tensor-parallel serving: params laid out by the SAME rules as
            # training (parallel/sharding.py) and the KV pool sharded over
            # heads on the model axis — GSPMD then compiles every engine
            # program (prefill/implant/chunk) with the right collectives.
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from kubeflow_tpu.parallel.sharding import transformer_rules

            rules = rules or transformer_rules(fsdp=False)
            specs = rules(params)
            mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
            rules.validate_divisibility(params, mesh_shape)
            # the KV pool shards its head axis P(None,'model',None) over
            # kv_heads — validate_divisibility only sees PARAMS, so a GQA
            # config with kv_heads % model-size != 0 would otherwise die
            # later inside the jitted cache init with an opaque GSPMD error
            model_size = mesh_shape.get("model", 1)
            if cfg.kv_heads % model_size:
                raise ValueError(
                    f"TP serving shards the KV cache over kv_heads: "
                    f"kv_heads {cfg.kv_heads} must be divisible by the "
                    f"mesh 'model' axis size {model_size}"
                )
            self.params = jax.tree_util.tree_map(
                lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
                params, specs,
            )
        else:
            self.params = jax.device_put(params)
        self.max_batch, self.max_seq = max_batch, max_seq
        self.chunk_steps = chunk_steps
        self.prefill_buckets = tuple(sorted(prefill_buckets))
        self.eos_id, self.pad_id = eos_id, pad_id
        self.max_queue = max_queue
        if prefill_chunk is not None and (
            prefill_chunk < 16 or prefill_chunk % 16
        ):
            raise ValueError("prefill_chunk must be a multiple of 16")
        #: chunked prefill (vLLM analog): long prompts prefill in
        #: prefill_chunk-token pieces INTERLEAVED with decode chunks, so an
        #: admission never stalls in-flight rows for a whole long prefill.
        #: None = each prompt prefills in one piece (its full bucket).
        self.prefill_chunk = prefill_chunk
        self._prefilling: dict[int, dict] = {}
        self._rng = jax.random.PRNGKey(seed)

        # device state: the persistent cache. Everything per-row and small
        # (lengths, last tokens, activity) lives host-side as numpy — it
        # rides into each chunk call and costs nothing next to the cache.
        pages_per_row = -(-max_seq // page_size)
        if kv_pool_tokens is None:
            # no size named: every row can hold max_seq tokens at once —
            # the bytes of a (max_batch, max_seq) rectangle — so pages
            # never hold an admission back
            kv_pool_tokens = page_size * (
                max_batch * pages_per_row + PageAllocator.RESERVED_PAGES
            )
        self.pager = PageAllocator(
            pool_tokens=kv_pool_tokens,
            page_size=page_size,
            max_batch=max_batch,
            max_pages_per_row=pages_per_row,
        )

        def init_cache():
            return init_paged_kv_cache(
                cfg, kv_pool_tokens, kv_quant=self.kv_quant
            )

        if mesh is not None:
            # token-major pools (pool_tokens, kv_heads, D) — heads are
            # axis 1 — and, with int8 KV, rank-2 (kv_heads, pool_tokens)
            # scale arrays, so the sharding is a per-leaf tree (the heads
            # axis sharded in both). Allocated DIRECTLY in the sharded
            # layout: materialising the full tree on one device first
            # would OOM exactly the deployments TP serving exists for
            pool_sh = NamedSharding(mesh, P(None, "model", None))
            scale_sh = NamedSharding(mesh, P("model", None))
            self.cache = jax.jit(
                init_cache,
                out_shardings=jax.tree_util.tree_map(
                    lambda l: scale_sh if l.ndim == 2 else pool_sh,
                    jax.eval_shape(init_cache),
                ),
            )()
        else:
            self.cache = init_cache()
        self.real_len = np.zeros((max_batch,), np.int32)   # prompt length
        self.gen_count = np.zeros((max_batch,), np.int32)  # tokens so far
        self.budget = np.zeros((max_batch,), np.int32)     # max_new_tokens
        self.last_tok = np.zeros((max_batch,), np.int32)
        self.active = np.zeros((max_batch,), bool)
        self.temp = np.zeros((max_batch,), np.float32)
        #: per-row sampling seed (-1 = unseeded: legacy engine-RNG draws,
        #: bit-identical to the pre-resume engine). Seeded rows draw
        #: position-folded per-row keys, so their token stream is
        #: independent of batch composition, row index and RNG history —
        #: the property a cross-replica resume needs.
        self.seeds = np.full((max_batch,), -1, np.int32)
        #: host twin used to pick the static `seeded` program variant at
        #: chunk dispatch without a device sync; refreshed per carry build
        self._carry_seeded = False
        self._slots: list[_Request | None] = [None] * max_batch
        # speculative decoding: the host mirror of the per-row token
        # history (prompt + generated, TOKEN-POSITION indexed). The device
        # copy rides the carry
        # and is rewritten in-graph each decode step; this mirror (fed at
        # admission and from drained tokens) rebuilds it on every epoch
        # re-upload. Width max_seq + K + 1 gives the in-graph span write
        # (K+1 wide at index hist_len) guaranteed headroom — no clamping.
        self.hist_host = (
            np.zeros((max_batch, max_seq + self.spec_k + 1), np.int32)
            if self.spec_k
            else None
        )

        self._pending: queue.Queue[_Request] = queue.Queue()
        self._fatal: Exception | None = None
        #: watchdog poisoning: set (with the retryable EngineRestarting)
        #: while a supervised restart tears this instance down — submits
        #: racing the swap fail fast with the retryable error, not a 500
        self._poisoned: Exception | None = None
        self._lock = threading.Lock()
        self._work = threading.Event()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        #: scheduler-loop heartbeat (monotonic): stamped at the top of
        #: every loop iteration — the watchdog's wedge signal is this
        #: going stale while the engine has work
        self._beat = time.monotonic()
        #: chaos seam (chaos/injectors.py wedge_engine / slow_decode):
        #: a "pre_chunk" hook runs on the scheduler thread before each
        #: chunk dispatch. Production never populates this dict; the cost
        #: is one dict lookup per chunk.
        self._fault_hooks: dict[str, Any] = {}
        self.stats = {
            "admitted": 0, "completed": 0, "chunks": 0,
            "max_concurrent": 0, "prefix_hits": 0, "prefix_tokens_reused": 0,
            # cross-replica prefix-KV transfer (peer pull endpoints)
            "prefix_imported": 0, "prefix_exported": 0,
            "prefill_pieces": 0, "idle_wakes": 0,
            # of the pieces, those whose attention read the gathered
            # window through the flash forward kernel (the rest wrote
            # float32 scores over it): the model's own answer for the
            # piece's shape, `transformer.paged_flash_read`
            "prefill_pieces_flash_read": 0,
            # what the prefill programs computed: prompt tokens, and the
            # token slots they were padded to (pieces x piece length)
            "prefill_tokens": 0, "prefill_padded_tokens": 0,
            # speculative decoding: drafts proposed/accepted (the tokens-
            # per-forward multiplier — kft_engine_spec_*_total)
            "spec_proposed": 0, "spec_accepted": 0,
            # SRE layer: deadline retirements by stage + admission sheds
            # (pre-initialized: /metrics iterates from another thread)
            "deadline_expired_queued": 0, "deadline_expired_decoding": 0,
            "shed_deadline": 0, "shed_priority": 0,
            # mid-stream failover: requests admitted with a committed-
            # token resume prefix (kft_engine_resume_admits_total)
            "resume_admits": 0,
            # disaggregated prefill/decode: spans exported (prefill pool),
            # spans injected without a local prefill (decode pool), ship
            # bytes pulled, and ship failures degraded to local prefill
            "kv_spans_exported": 0, "kv_injected": 0,
            "kv_ship_bytes": 0, "kv_ship_fallbacks": 0,
            # host-RAM KV tier: sessions swapped out on finish / back in
            "kv_offload_out": 0, "kv_offload_in": 0,
            # most pool pages owned by resident rows at any admission
            "kv_pages_used_peak": 0,
            # the decode read path, counted at each chunk's dispatch: the
            # chunks whose attention read K and V through the Pallas
            # kernel (the rest gathered the window), the pages the active
            # rows hold up to their reach, and the pages of the window a
            # gather would read (max_batch x table width) — live / window
            # is the share of the window that exists
            "decode_chunks_kernel_read": 0,
            "decode_pages_live": 0, "decode_pages_window": 0,
            # beside them, what the active rows hold over all layers (pages
            # x layers) and, of that, the pages lying wholly before a
            # window layer's window: held in vain on the one table every
            # layer shares — what a per-kind allocator would free
            "kv_pages_held": 0, "kv_pages_dead_window": 0,
            # the paged kernel's grid in one decode step over all layers
            # (`_kernel_grid`), summed at each chunk's dispatch where the
            # chunk reads through the kernel: its length, and the steps of
            # it that stage a page a row reads
            "decode_kernel_steps": 0, "decode_kernel_steps_live": 0,
            # expert layers' routing of LIVE rows only (pad slots and dead
            # rows excluded), decode chunks and prefill pieces apart:
            # (token, expert) assignments, distinct (layer, step, expert)
            # triples touched, the layer-steps they are summed over, and
            # the fullest expert's assignments summed over layer-steps
            **{
                f"moe_{name}_{phase}": 0
                for name in _MOE_COUNTERS for phase in ("decode", "prefill")
            },
            # carry rebuilds a host edit caused (admission, a first token,
            # a host retirement), and of them those made with nothing in
            # flight: the pipeline drained first (the batch ran empty, the
            # end of a burst, pipeline_depth=0) instead of merging
            "epochs": 0, "epoch_drains": 0,
            # the scheduler thread's wall time, once per loop iteration, and
            # under it each phase's self seconds and entries (_phase)
            "sched_loop_s": 0.0,
            **{f"sched_{name}_s": 0.0 for name in _SCHED_PHASES},
            **{f"sched_{name}_n": 0 for name in _SCHED_PHASES},
        }
        #: open phases, innermost last: seconds spent in phases nested in each
        self._phase_nested: list[float] = []
        # pipelined-decode state: the device-resident carry of per-row
        # scheduling arrays, its dirtiness (host edits pending merge), and
        # the paged horizon bookkeeping for speculative chunks. ``overlap``
        # holds the pipeline gauges exported as kft_engine_* (obs/names.py).
        self._carry: dict[str, Any] | None = None
        self._carry_dirty = True
        #: rows whose host mirrors are newer than the device carry's
        #: (admitted since the last carry build): the merge takes them
        #: from the host even where the host says they decode
        self._carry_edit = np.zeros((max_batch,), bool)
        #: final prefill pieces dispatched and not yet read: (row, request,
        #: first token, its validity, routing counts), device handles
        self._firsts: list[tuple] = []
        #: the int8 pool's quantization error of each piece, unread
        self._qerrs: list = []
        #: a (token, validity) pair of device scalars that stands in the
        #: merge for every row without a first token: the merge program's
        #: arguments then have one structure, and — a real piece's outputs
        #: once there is one — the same placement as a first token's
        self._filler: tuple | None = None
        self._carry_chunks = 0   # chunks dispatched since last upload
        self._carry_h0 = 0       # max(real_len+gen_count) at upload, plus
                                 # the in-flight chunk's span when merged
        self._carry_hcap = 0     # max(real_len+budget) at upload
        self._carry_pages_w = 0  # uploaded table width (pages)
        self._last_dispatch: float | None = None
        self.overlap = {
            "decode_gap_ms": 0.0,   # EWMA host time between chunk dispatches
            "d2h_drain_ms": 0.0,    # EWMA token-drain D2H sync time
            "carry_uploads": 0,     # epoch re-uploads (~admissions, not chunks)
            "slot_occupancy": 0.0,  # EWMA occupied-row fraction at dispatch
            "spec_acceptance": 0.0,  # EWMA accepted/proposed draft ratio
        }
        if self.kv_quant == "int8":
            # EWMA of mean-abs relative KV quantization error, measured by
            # the suffix-prefill program (kft_engine_kv_quant_error)
            self.overlap["kv_quant_error"] = 0.0

        #: host-RAM KV tier (serve/kv_tier.py): finished sessioned rows
        #: swap their KV span out through the npz codec into this bounded
        #: host pool; the session's next turn swaps it back in via the
        #: prefix-implant machinery. The D2H + encode runs on a dedicated
        #: offload worker thread so a swap-out never stalls the scheduler.
        self.host_kv_tier = (
            HostKVTier(config.host_kv_bytes)
            if config.host_kv_bytes > 0 else None
        )
        self._offload_q: "queue.Queue | None" = (
            queue.Queue() if self.host_kv_tier is not None else None
        )
        self._offload_thread: threading.Thread | None = None

        # prefix cache (vLLM automatic-prefix-caching analog): completed
        # prompt prefills donate their KV, keyed by the prompt ids rounded
        # DOWN to a 16-token multiple — quantizing keeps the compiled
        # extract/implant/suffix-prefill programs to a bounded shape set
        # and the reused region contiguous (no junk slots mid-row).
        from collections import OrderedDict

        self._prefix_cache: "OrderedDict[tuple, dict] | None" = (
            OrderedDict() if prefix_cache_entries > 0 else None
        )
        #: guards the prefix-cache maps: the scheduler thread stores and
        #: looks up on every admission, while the peer-transfer endpoints
        #: (serve/server.py prefix_cache:pull/:export) index, import and
        #: export from HTTP executor threads
        self._prefix_lock = threading.Lock()
        #: public flag for the peer-transfer endpoints (serve/server.py):
        #: set once here, never mutated
        self.prefix_cache_enabled = prefix_cache_entries > 0
        self._prefix_cache_entries = prefix_cache_entries
        self._prefix_cache_tokens = prefix_cache_tokens
        self._prefix_lens: dict[int, int] = {}  # stored length → count
        #: descending stored lengths, memoized — _lookup_prefix runs on
        #: every admission, so it must not pay an O(L log L) sort per
        #: request; store/evict invalidate (None → rebuild on next probe)
        self._prefix_lens_sorted: list[int] | None = None
        self._prefix_tokens_stored = 0

        # ONE prefill program: a full prefill IS a suffix prefill at
        # offset 0 (same mask, same rope coordinates) — no second copy to
        # keep in sync. The cache argument is DONATED everywhere: without
        # donation every prefill/implant/chunk call copies the entire
        # (pool_tokens, kv_heads, D) x layers x 2 KV tree — pure HBM
        # bandwidth waste since the engine always rebinds self.cache to
        # the result. Donation alone is not enough: a program that
        # computes in another layout than its arguments arrive in copies
        # the donated array in and out all the same, which is why the
        # paged pool is stored token-major, the order its scatters and
        # gathers index (models/transformer.py::init_paged_kv_cache).
        # (A failed donated call kills the buffers; the
        # scheduler's fatal path already fails all requests and the
        # engine is rebuilt on reload.)
        # the spec chunk programs donate the history buffer alongside the
        # cache: both are engine-owned device state rebound to the call's
        # result every chunk (never Orbax-restored), so donation is safe
        # and saves a (B, max_seq) copy per chunk.
        # ``params`` is argument 0 of every model-running program, never a
        # closed-over tree: jit bakes what it closes over into the program
        # as constants — the whole model inside each program's HLO and
        # cache key, once per program (found on the chip: the first 1 GB
        # program compile outlasted the watchdog's wedge floor).
        chunk_donate = (1, 2) if self.spec_k else (1,)
        # ``seeded`` is a STATIC specialization knob: the seeded variant of
        # each program (extra per-step position-folded PRNG draws) only
        # compiles — and only runs — when a seeded row is actually in the
        # batch; pure-unseeded traffic stays on programs byte-identical to
        # the pre-resume engine.
        self._suffix_prefill = jax.jit(
            self._suffix_prefill_paged_impl, donate_argnums=(1,),
            static_argnames=("seeded",),
        )
        self._chunk = jax.jit(
            self._chunk_spec_paged_impl if self.spec_k
            else self._chunk_paged_impl,
            donate_argnums=chunk_donate, static_argnames=("seeded",),
        )
        self._merge = jax.jit(self._merge_carry_impl)
        #: the model's programs are traced and run with the engine's mesh
        #: in force, so what they decide by it (the paged read path: a
        #: Mosaic kernel is not partitioned automatically) they decide
        #: knowing it is there
        self._mesh_scope = (
            contextlib.nullcontext if mesh is None
            else (lambda: jax.set_mesh(mesh))
        )
        with self._mesh_scope():
            #: whether the decode chunk's attention reads through the
            #: Pallas kernel: the model's own answer for the chunk's span
            self.kernel_read = paged_kernel_read(
                cfg, self.max_batch, self.spec_k + 1
            )
        #: layers by their window (None: a global layer): what the dead
        #: pages and the paged kernel's grid counted at each chunk's
        #: dispatch are summed over
        self._layer_windows = collections.Counter(
            kind.window for kind in cfg.kinds
        )
        #: the tile the paged kernel takes at a table width, for the
        #: chunk's span (the call's own rule, `paged_attention`)
        pool = next(iter(self.cache.values()))["k"]
        self._kernel_tile = functools.partial(
            select_paged_geometry, page_size=self.page_size,
            kv_heads=cfg.kv_heads, groups=cfg.n_heads // cfg.kv_heads,
            span=self.spec_k + 1, head_dim=cfg.head_dim,
            itemsize=pool.dtype.itemsize, quant=self.kv_quant == "int8",
        )
        self._implant_jits: dict[int, Any] = {}
        #: a request held back by page backpressure (FIFO preserved:
        #: nothing admits past it until its pages free up)
        self._held: "_Request | None" = None
        self._extract_jits: dict[int, Any] = {}

    # -- device programs ---------------------------------------------------- #

    def _seeded_sample(self, logits, seed, pos, temperature, legacy):
        """Per-row deterministic sampling for the mid-stream resume
        contract: a seeded row (seed >= 0) draws the token at absolute
        position ``pos`` from ``fold_in(PRNGKey(seed), pos)`` — a function
        of (seed, position, logits) only, independent of batch
        composition, row index and engine RNG history, so a resumed
        stream on ANY replica continues the exact sampling stream the
        dead one began. Unseeded rows (seed < 0) keep ``legacy`` (the
        engine-RNG draw computed by the caller) bit-identically; greedy
        seeded rows reduce to argmax, which every replica agrees on."""
        def draw(s, p, lg, t):
            key = jax.random.fold_in(jax.random.PRNGKey(s), p)
            return jax.random.categorical(key, lg / jnp.maximum(t, 1e-6))

        drawn = jax.vmap(draw)(seed, pos, logits, temperature)
        greedy = jnp.argmax(logits, axis=-1).astype(drawn.dtype)
        seeded = jnp.where(temperature <= 0.0, greedy, drawn)
        return jnp.where(seed >= 0, seeded, legacy.astype(drawn.dtype))

    def _extract_prefix(self, row: int, n16: int):
        """Copy row ``row``'s first n16 KV tokens out as a (1, kv_heads,
        n16, D)-per-layer entry (one jit per n16 — the 16-multiple
        quantization bounds this set): gathered through the block table
        and transposed out of the pool's token-major order. This entry
        format is the prefix store's, the KV span's and the host tier's."""
        fn = self._extract_jits.get(n16)
        if fn is None:
            P = self.page_size
            quant = self.kv_quant == "int8"

            def impl(cache, table_row):
                j = jnp.arange(n16)
                idx = table_row[j // P] * P + j % P
                out = {
                    name: {
                        "k": lc["k"][idx].transpose(1, 0, 2)[None],
                        "v": lc["v"][idx].transpose(1, 0, 2)[None],
                    }
                    for name, lc in cache.items()
                }
                if quant:
                    # int8 entries carry their per-token scales —
                    # (1, kv_heads, n16) alongside the (1, kv_heads,
                    # n16, D) codes — so an imported prefix dequants
                    # identically on the receiving engine
                    for name, lc in cache.items():
                        out[name]["k_scale"] = lc["k_scale"][:, idx][None]
                        out[name]["v_scale"] = lc["v_scale"][:, idx][None]
                return out

            fn = self._extract_jits[n16] = jax.jit(impl)
        return fn(self.cache, jnp.asarray(self.pager.table[row].copy()))

    # -- speculative decoding (serve/speculative.py) ------------------------- #

    def _spec_emit(
        self, emitted, n_emit, draft_len, n_acc, tok, gen_count, active,
        budget,
    ):
        """Shared post-verify gating for one speculative decode step:
        apply the liveness/budget/EOS rules of the classic one-token step
        to the whole emitted span. Position i of the span is *live* iff
        the row was live entering the step, the position was actually
        emitted (i < n_emit), budget admits it (gen_count + i < budget),
        and no live EOS landed earlier in the span; live positions
        consume budget exactly like single-token steps, EOS positions are
        live-but-invalid (budget charged, token not emitted — today's
        semantics), and everything after a live EOS is dead."""
        K1 = self.spec_k + 1
        i = jnp.arange(K1)[None, :]
        live0 = active & (gen_count < budget)
        cand = (
            live0[:, None]
            & (i < n_emit[:, None])
            & (gen_count[:, None] + i < budget[:, None])
        )
        is_eos = emitted == self.eos_id
        eos_here = (cand & is_eos).astype(jnp.int32)
        no_eos_before = jnp.concatenate(
            [
                jnp.ones_like(eos_here[:, :1]),
                jnp.cumprod(1 - eos_here, axis=1)[:, :-1],
            ],
            axis=1,
        ).astype(bool)
        live_i = cand & no_eos_before                       # (B, K+1)
        valid_i = live_i & ~is_eos
        out = jnp.where(valid_i, emitted, self.pad_id)
        adv = live_i.sum(axis=1).astype(gen_count.dtype)    # (B,)
        eos_step = (live_i & is_eos).any(axis=1)
        # carry token: the last VALID emitted token (frozen through EOS /
        # dead steps, exactly like the one-token step's jnp.where chain)
        last_idx = jnp.clip(adv - 1, 0, K1 - 1)
        last_out = jnp.take_along_axis(out, last_idx[:, None], axis=1)[:, 0]
        last_ok = jnp.take_along_axis(
            valid_i, last_idx[:, None], axis=1
        )[:, 0]
        new_tok = jnp.where((adv > 0) & last_ok, last_out, tok)
        new_gen = gen_count + adv
        new_active = active & ~eos_step
        # telemetry planes, gated to live rows so post-retirement SPMD
        # steps don't inflate the acceptance gauges
        prop = jnp.where(live0, draft_len, 0)
        acc = jnp.where(live0, jnp.minimum(n_acc, adv), 0)
        return (
            out, valid_i, live_i, eos_step, new_tok, new_gen, new_active,
            prop, acc,
        )

    def _spec_hist_update(self, hist, hist_len, emitted, live_i):
        """Scatter the span's live emitted tokens into each row's history
        at positions [hist_len, hist_len + K]. hist is max_seq + K + 1
        wide, so the window never clamps (a clamped start would shift the
        write over real history)."""
        K1 = self.spec_k + 1

        def upd(hrow, start, vals, mask):
            win = jax.lax.dynamic_slice(hrow, (start,), (K1,))
            return jax.lax.dynamic_update_slice(
                hrow, jnp.where(mask, vals, win), (start,)
            )

        return jax.vmap(upd)(hist, hist_len, emitted, live_i)

    def _chunk_spec_paged_impl(
        self, params, cache, hist, last_tok, real_len, gen_count, active,
        budget, temperature, seed, rng, table, *, seeded=False,
    ):
        """Speculative form of _chunk_paged_impl: each scan step drafts up
        to K tokens by prompt-lookup against the row's device-resident
        history and verifies them in ONE (K+1)-position forward through
        the block table, positions (L-1 .. L-1+K) per row — masking is
        position arithmetic, already per query. Accepted drafts' KV is
        already correct (they were the forward's inputs); rejected
        positions' KV lands beyond the accepted pointer where later steps
        re-overwrite it before it is ever attended. Span positions past
        the row's budgeted region route to the scratch page (their page
        ordinal may sit past the read window, where a clamped gather
        would otherwise redirect the write INTO the row's real pages).
        Rows with no match draft length 0 and degrade to the classic
        one-token step."""
        from kubeflow_tpu.serve.speculative import propose_draft, spec_accept

        K = self.spec_k

        def step(carry, _):
            cache, hist, tok, gen_count, active, rng = carry
            rng, sub = jax.random.split(rng)
            live0 = active & (gen_count < budget)
            L = real_len + gen_count
            draft, draft_len = propose_draft(
                hist, L, ngram=self.spec_ngram, k=K
            )
            # seeded temperature>0 rows must not speculate: spec_accept's
            # batched accept/resample draws are coupled to batch RNG
            # history, which breaks the cross-replica resume-determinism
            # contract. Force draft length 0 (the classic one-token step)
            # and draw the emitted token per-row below. Greedy seeded
            # rows keep speculating — argmax needs no RNG.
            if seeded:
                seeded_t = (seed >= 0) & (temperature > 0.0)
                draft_len = jnp.where(seeded_t, 0, draft_len)
            x = jnp.concatenate([tok[:, None], draft], axis=1)
            positions = (L - 1)[:, None] + jnp.arange(K + 1)[None, :]
            write_ok = live0[:, None] & (
                positions < (real_len + budget)[:, None]
            )
            lg, cache, moe, _ = self._forward(
                params, x, cache, positions=positions, page_table=table,
                page_write_ok=write_ok,
            )
            emitted, n_emit, n_acc = spec_accept(
                lg, draft, draft_len, sub, temperature
            )
            # span position 0's absolute position is L: override it with
            # the position-folded draw (seeded rows only; for greedy
            # seeded rows this is argmax(lg[:,0]) == what spec emitted)
            if seeded:
                emitted = emitted.at[:, 0].set(self._seeded_sample(
                    lg[:, 0], seed, L, temperature, emitted[:, 0]
                ))
            (
                out, valid_i, live_i, eos_step, tok, gen_count, active,
                prop, acc,
            ) = self._spec_emit(
                emitted, n_emit, draft_len, n_acc, tok, gen_count, active,
                budget,
            )
            hist = self._spec_hist_update(hist, L, emitted, live_i)
            return (cache, hist, tok, gen_count, active, rng), (
                out, valid_i, eos_step, prop, acc, moe,
            )

        (cache, hist, tok, gen_count, active, _), outs = jax.lax.scan(
            step,
            (cache, hist, last_tok, gen_count, active, rng),
            None,
            length=self.chunk_steps,
        )
        toks, valid, eos, prop, acc, moe = outs
        return (
            cache, hist, tok, gen_count, active,
            jnp.moveaxis(toks, 0, 1), jnp.moveaxis(valid, 0, 1),
            eos.T, prop.T, acc.T,
        ) + self._moe * (jax.tree_util.tree_map(lambda x: x.sum(0), moe),)

    # -- device programs through the block table (serve/paging.py) ---------- #

    def _forward(self, params, tokens, cache, *, quant_stats=False, **kw):
        """The model through the block table: ``(logits, cache, moe,
        qerr)``. ``moe``: the routing counts of this call's live tokens
        where the model has expert layers, else None (``_moe_counts``);
        ``qerr``: the int8 pool's quantization error (abs, den) where
        asked for. A model with neither is applied exactly as before."""
        mutable = ["moe_stats"] * self._moe + ["quant_stats"] * quant_stats
        variables = {"params": params}
        kw = dict(
            kw, cache=cache, page_size=self.page_size, kv_quant=self.kv_quant
        )
        if not mutable:
            logits, cache = self.model.apply(variables, tokens, **kw)
            return logits, cache, None, None
        (logits, cache), sown = self.model.apply(
            variables, tokens, mutable=mutable, **kw
        )
        leaves = jax.tree_util.tree_leaves
        with jax.named_scope(MOE_STATS):
            moe = self._moe_counts(leaves(sown["moe_stats"])) if self._moe else None
        return (
            logits, cache, moe,
            sum(leaves(sown["quant_stats"])) if quant_stats else None,
        )

    @staticmethod
    def _moe_counts(per_layer):
        """One forward's routing, from each expert layer's assignments per
        expert (live tokens only): the vector summed over layers, the
        (layer, expert) pairs touched, the layers that had a live token,
        and the fullest expert's assignments summed over layers."""
        return {
            "per_expert": sum(per_layer),
            "experts_touched": sum((c > 0).sum() for c in per_layer),
            "layer_steps": sum((c.sum() > 0).astype(jnp.int32) for c in per_layer),
            "load_max": sum(c.max() for c in per_layer),
        }

    def _count_moe(self, counts: list, phase: str) -> dict:
        """Fold drained ``_moe_counts`` — a chunk's, summed over its steps,
        or one of each of a request's pieces — into ``stats``; returns
        their sum as host ints."""
        moe = {
            k: sum(np.asarray(c[k]) for c in counts)  # kft: noqa[jax-sync] — rides the token drain (a chunk) or the final piece's sample (a prefill)
            for k in counts[0]
        }
        self.moe_expert_load += moe["per_expert"]
        out = {"assignments": int(moe["per_expert"].sum())}
        out.update({k: int(moe[k]) for k in _MOE_COUNTERS[1:]})
        for name, value in out.items():
            self.stats[f"moe_{name}_{phase}"] += value
        return out

    def _pages_w(self, tokens: int) -> int:
        """Read-window width in pages: pow2-rounded so the compiled
        program set stays bounded, capped at the per-row maximum."""
        need = -(-tokens // self.page_size)
        w = 1
        while w < need:
            w *= 2
        return min(w, self.pager.max_pages_per_row)

    def _suffix_prefill_paged_impl(
        self, params, cache, suffix, slen, offset, table, temperature,
        seed, pos, rng, *, seeded=False,
    ):
        """One row's prefill piece: writes tokens [offset, offset+S)
        through its block table, the first ``offset`` tokens being a
        reused prefix or earlier pieces (a full prefill is the piece at
        offset 0). Pad positions (>= slen) route to the scratch page. The
        read window is ``table`` width × page_size (pow2-bucketed by the
        caller)."""
        S = suffix.shape[1]
        with jax.named_scope(CARRY):
            positions = offset + jnp.arange(S)[None, :]      # (1, S)
            write_ok = (jnp.arange(S) < slen[:, None])       # (1, S)
        # the ONLY program that materializes the quantization-error
        # telemetry the model sows (abs, den): per-admission amortization,
        # and the scan-carry chunk programs stay telemetry-free. The head
        # is computed at the one position whose logits are sampled: the
        # piece's other S - 1 rows of (S, vocab) are never built
        with jax.named_scope(HEAD):
            wanted = (slen - 1)[:, None]
        logits, cache, moe, qerr = self._forward(
            params, suffix, cache, quant_stats=self.kv_quant == "int8",
            positions=positions, page_table=table, page_write_ok=write_ok,
            logit_positions=wanted,
        )
        if qerr is None:
            qerr = jnp.zeros((2,), jnp.float32)
        with jax.named_scope(SAMPLE):
            last = logits[:, 0]
            tok = _sample(last, rng, temperature[None])
            if seeded:
                tok = self._seeded_sample(
                    last, jnp.asarray(seed, jnp.int32)[None],
                    jnp.asarray(pos, jnp.int32)[None], temperature[None], tok,
                )
            tok = tok[0]
            live = tok != self.eos_id
        return (cache, tok, live, qerr) + self._moe * (moe,)

    def _implant_paged(self, stored, row: int, n16: int):
        """Scatter a stored prefix (1, kv_heads, n16, D per layer —
        _extract_prefix's entry format, transposed here into the pool's
        token-major order) into row ``row``'s pages at token indices
        [0, n16)."""
        fn = self._implant_jits.get(n16)
        if fn is None:
            P = self.page_size
            quant = self.kv_quant == "int8"

            def impl(cache, stored, table_row):
                j = jnp.arange(n16)
                idx = table_row[j // P] * P + j % P
                out = {
                    name: {
                        "k": cache[name]["k"].at[idx].set(
                            stored[name]["k"][0].transpose(1, 0, 2).astype(
                                cache[name]["k"].dtype
                            )
                        ),
                        "v": cache[name]["v"].at[idx].set(
                            stored[name]["v"][0].transpose(1, 0, 2).astype(
                                cache[name]["v"].dtype
                            )
                        ),
                    }
                    for name in cache
                }
                if quant:
                    for name in cache:
                        out[name]["k_scale"] = (
                            cache[name]["k_scale"].at[:, idx].set(
                                stored[name]["k_scale"][0]
                            )
                        )
                        out[name]["v_scale"] = (
                            cache[name]["v_scale"].at[:, idx].set(
                                stored[name]["v_scale"][0]
                            )
                        )
                return out

            fn = self._implant_jits[n16] = jax.jit(
                impl, donate_argnums=(0,)
            )
        self.cache = fn(
            self.cache, stored, jnp.asarray(self.pager.table[row].copy())
        )

    def _chunk_paged_impl(
        self, params, cache, last_tok, real_len, gen_count, active, budget,
        temperature, seed, rng, table, *, seeded=False,
    ):
        """``chunk_steps`` decode steps for ALL rows. A row's token space
        is CONTIGUOUS (gen token g sits at token index real_len + g), so
        position == token index and the model's paged branch derives
        causal/window masking from positions alone. The carry token is
        the LAST EMITTED one: its KV lands at its own index and attention
        sees everything up to it. Inactive and over-budget rows still
        step (SPMD: no dynamic batch) but never advance or emit valid
        tokens, and their writes route to the scratch page — their pages
        may already belong to another row."""

        def step(carry, _):
            cache, tok, gen_count, active, rng = carry
            with jax.named_scope(SAMPLE):
                rng, sub = jax.random.split(rng)
            with jax.named_scope(CARRY):
                live = active & (gen_count < budget)         # (B,)
                cur = real_len + gen_count - 1               # (B,) token idx
            lg, cache, moe, _ = self._forward(
                params, tok[:, None], cache, positions=cur[:, None],
                page_table=table, page_write_ok=live[:, None],
            )
            with jax.named_scope(SAMPLE):
                nxt = _sample(lg[:, 0], sub, temperature)
                if seeded:
                    nxt = self._seeded_sample(
                        lg[:, 0], seed, real_len + gen_count, temperature, nxt
                    )
            with jax.named_scope(CARRY):
                valid = live & (nxt != self.eos_id)
                out = jnp.where(valid, nxt, self.pad_id)
                gen_count = jnp.where(live, gen_count + 1, gen_count)
                tok = jnp.where(valid, out, tok)
            return (cache, tok, gen_count, valid, rng), (out, valid, moe)

        (cache, tok, gen_count, active, _), (toks, valid, moe) = jax.lax.scan(
            step,
            (cache, last_tok, gen_count, active, rng),
            None,
            length=self.chunk_steps,
        )
        # (B, T) tokens; beside them, where the model routes, the chunk's
        # routing counts summed over its steps
        with jax.named_scope(CARRY):
            toks, valid = toks.T, valid.T
        with jax.named_scope(MOE_STATS):
            moe = jax.tree_util.tree_map(lambda x: x.sum(0), moe)
        return (cache, tok, gen_count, active, toks, valid) + self._moe * (moe,)

    def _merge_carry_impl(
        self, last_tok, gen_count, active, host, toks, valids,
        hist=None, hist_host=None, real_len=None,
    ):
        """The epoch's carry of the rows' decode state, built on the
        device. ``host`` is (4, B): the source of each row (``_KEEP``,
        ``_HOST``, ``_FIRST``) and the host mirrors of its last token,
        generation count and liveness. A kept row takes the carry given
        (the in-flight chunk's outputs); a host row its mirrors; a first
        row its final piece's token (``toks``, one device scalar a row),
        one token generated, and live if the host lets it decode and the
        token is not EOS (``valids``). Under speculation the history is
        merged the same way, with a first row's token written at its
        prompt's end."""
        with jax.named_scope(MERGE):
            mode, h_last, h_gen, h_act = host
            keep, first = mode == _KEEP, mode == _FIRST
            tok = jnp.stack(toks).astype(last_tok.dtype)
            valid = jnp.stack(valids)
            last_tok = jnp.where(keep, last_tok, jnp.where(first, tok, h_last))
            gen_count = jnp.where(keep, gen_count, jnp.where(first, 1, h_gen))
            active = jnp.where(keep, active, (h_act != 0) & (valid | ~first))
            if hist is None:
                return last_tok, gen_count, active
            col = jnp.arange(hist.shape[1])[None, :]
            hist = jnp.where(keep[:, None], hist, hist_host)
            put = (first & valid)[:, None] & (col == real_len[:, None])
            return last_tok, gen_count, active, jnp.where(put, tok[:, None], hist)

    # -- host scheduler ----------------------------------------------------- #

    def start(self) -> "LMEngine":
        if self.host_kv_tier is not None and self._offload_thread is None:
            self._offload_thread = threading.Thread(
                target=self._offload_loop, name="kv-offload", daemon=True
            )
            self._offload_thread.start()
        self._thread = threading.Thread(
            target=self._loop, name="lm-engine", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._work.set()
        if self._thread is not None:
            self._thread.join(30)
        if self._offload_thread is not None:
            self._offload_q.put(None)  # drain-then-exit sentinel
            self._offload_thread.join(10)
            self._offload_thread = None
        # anything still queued or mid-generation must not hang its caller
        # until timeout_s — fail it with the truth now
        err = RuntimeError("LM engine stopped")
        for row in range(self.max_batch):
            req = self._slots[row]
            if req is not None:
                self._slots[row] = None
                req.error = err
                req.finish()
        if self._held is not None:
            self._held.error = err
            self._held.finish()
            self._held = None
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            req.error = err
            req.finish()

    # -- SRE surface: liveness, poisoning, admission estimation ------------- #

    def heartbeat(self) -> float:
        """Monotonic stamp of the scheduler loop's last iteration start."""
        return self._beat

    def busy(self) -> bool:
        """True when the engine has work a wedged loop would be stalling:
        active decode rows, queued admissions, prefills in flight, or a
        page-held request."""
        return bool(
            self.active.any()
            or self._pending.qsize()
            or self._prefilling
            or self._held is not None
        )

    def poison(self, err: Exception) -> None:
        """Fail every in-flight and queued request with ``err`` NOW and
        stop accepting work — WITHOUT joining the scheduler thread (it
        may be wedged inside a device call; it observes ``_stop`` when
        the call returns and exits on its own). The watchdog calls this
        before rebuilding; the drain mirrors the fatal path."""
        self._poisoned = err
        self._stop.set()
        self._work.set()
        for row in range(self.max_batch):
            req = self._slots[row]
            if req is not None:
                self._slots[row] = None
                req.error = err
                req.finish()
        if self._held is not None:
            self._held.error = err
            self._held.finish()
            self._held = None
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            req.error = err
            req.finish()

    def estimate_admission(
        self, max_new_tokens: int
    ) -> tuple[float, float] | None:
        """(queue_wait_s, decode_s) estimate for a request admitted now,
        from the decode-gap EWMA the pipelined loop already tracks. None
        while the EWMA is cold (no evidence → never shed on a guess).

        ``decode_s`` uses the chunk *span* (steps × K+1 under
        speculation) — an upper bound on tokens per chunk, so the shed
        decision errs toward admitting. ``queue_wait_s`` models the
        backlog as admission waves: requests queued ahead of this one
        drain ``max_batch`` at a time, each wave lasting the mean
        remaining decode time of the currently active rows."""
        gap_s = self.overlap["decode_gap_ms"] / 1e3
        if gap_s <= 0.0:
            return None
        span = self._chunk_span
        decode_s = -(-max_new_tokens // span) * gap_s
        queued = self._pending.qsize() + (
            1 if self._held is not None else 0
        )
        free = sum(s is None for s in self._slots)
        if queued < free:
            return 0.0, decode_s
        act = self.active
        if act.any():
            mean_remaining = float(
                (self.budget - self.gen_count)[act].mean()
            )
        else:
            mean_remaining = float(max_new_tokens)
        wave_s = max(1.0, mean_remaining / span) * gap_s
        waves = -(-(queued + 1 - free) // self.max_batch)
        return waves * wave_s, decode_s

    def _enqueue(
        self, ids, max_new_tokens, temperature, *, live: bool,
        deadline: float | None = None, priority: int = 0,
        trace: Any = None, want_kv_span: bool = False,
        kv_inject: PreparedKVSpan | None = None,
        session: str | None = None,
        resume: int = 0,
        seed: int | None = None,
    ) -> _Request:
        if not ids:
            raise ValueError("empty prompt")
        if self._poisoned is not None:
            raise self._poisoned
        if self._fatal is not None:
            raise RuntimeError("LM engine is dead") from self._fatal
        if self._stop.is_set():
            # a submit racing (or following) stop() must fail NOW — the
            # scheduler thread is gone and nothing would ever service it
            raise RuntimeError("LM engine stopped")
        if deadline is not None:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                DEADLINE_EXPIRED.labels(stage="admission").inc()
                raise DeadlineExceeded(
                    "deadline already expired at admission",
                    stage="admission",
                )
            est = self.estimate_admission(max_new_tokens)
            if est is not None:
                queue_wait_s, decode_s = est
                if queue_wait_s + decode_s > remaining:
                    # shed BEFORE the request costs a decode slot: by the
                    # throughput evidence in hand it cannot finish inside
                    # its budget — 503 + Retry-After (backlog drain time)
                    self.stats["shed_deadline"] += 1
                    ADMISSION_SHED.labels(reason="deadline_unmeetable").inc()
                    raise AdmissionShed(
                        f"deadline unmeetable: ~{queue_wait_s:.1f}s queue "
                        f"+ ~{decode_s:.1f}s decode > {remaining:.1f}s "
                        "remaining",
                        reason="deadline_unmeetable",
                        retry_after_s=queue_wait_s,
                    )
        # bounded admission: total outstanding work (rows decoding + queue)
        # beyond max_batch + max_queue is shed — an unbounded tail would
        # wait longer than any client timeout
        occupied = sum(s is not None for s in self._slots)
        held = 1 if self._held is not None else 0
        if (
            self._pending.qsize() + occupied + held
            >= self.max_batch + self.max_queue
        ):
            if not self._evict_lower_priority(priority):
                raise EngineOverloaded(
                    f"engine at capacity ({occupied} decoding, "
                    f"{self._pending.qsize() + held} queued, "
                    f"max_queue={self.max_queue})"
                )
        # a row's token space is contiguous (no bucket- or piece-padding
        # gap: padding writes to the scratch page), so admission is by the
        # prompt's own length. max_seq FIRST: a request over the per-row
        # bound must say so — "raise kv_pool_tokens" would be a lie when no
        # pool size can fit it in the page-table width
        if len(ids) + max_new_tokens > self.max_seq:
            raise ValueError(
                f"prompt length {len(ids)} + max_new_tokens "
                f"{max_new_tokens} exceeds engine max_seq {self.max_seq}"
            )
        need = self.pager.pages_for(len(ids) + max_new_tokens)
        if need > self.pager.usable_pages:
            raise ValueError(
                f"request needs {need} pages; pool has "
                f"{self.pager.usable_pages} — raise kv_pool_tokens"
            )
        if self.prefill_chunk is None and kv_inject is None:
            self._bucket(len(ids))  # reject over-bucket prompts now
        req = _Request(
            list(ids), max_new_tokens, temperature,
            live=queue.Queue() if live else None,
            deadline=deadline, priority=priority,
            want_kv_span=want_kv_span, kv_inject=kv_inject,
            session=session, resume=resume, seed=seed,
        )
        if resume:
            # the engine half of a gateway mid-stream failover: ids
            # already contain the committed tokens
            self.stats["resume_admits"] += 1
            RESUME_ADMITS.labels(model=self.model_name).inc()
        if trace is not None:
            # engine-stage span under the caller's wire context (a Span or
            # a parsed TraceContext — both carry trace_id/span_id); its
            # queue.wait child covers admission-queue time and is closed
            # by _admit
            espan = TRACER.span("engine", parent=trace)
            if espan:
                espan.set_attr("model", self.model_name)
                espan.set_attr("prompt_tokens", len(req.ids))
                espan.set_attr("max_new_tokens", max_new_tokens)
                if priority:
                    espan.set_attr("priority", priority)
                if resume:
                    espan.set_attr("resume_tokens", resume)
                req.model = self.model_name
                req.espan = espan
                req.qspan = TRACER.span("queue.wait", parent=espan)
                req.t_enqueue = time.monotonic()
        self._pending.put(req)
        self._work.set()
        if (
            self._stop.is_set() or self._fatal is not None
        ) and not req.done.is_set():
            # raced stop()'s or the crash handler's drain: fail it ourselves
            # (double-finish from the drain is harmless — idempotent events)
            req.error = RuntimeError("LM engine stopped")
            if self._fatal is not None:
                req.error = RuntimeError("LM engine is dead")
                req.error.__cause__ = self._fatal
            req.finish()
        return req

    def _evict_lower_priority(self, priority: int) -> bool:
        """Under overload, shed the lowest-priority queued request whose
        priority is strictly below the newcomer's — lowest-priority
        tenants brown out first instead of FIFO arrival luck deciding.
        Returns True when a slot was freed. Only QUEUED requests are
        victims: evicting an active row would waste decode work."""
        with self._pending.mutex:
            victim = None
            for cand in self._pending.queue:
                if cand.done.is_set() or cand.cancelled.is_set():
                    continue
                if cand.priority < priority and (
                    victim is None or cand.priority < victim.priority
                ):
                    victim = cand
            if victim is None:
                return False
            self._pending.queue.remove(victim)
        self.stats["shed_priority"] += 1
        ADMISSION_SHED.labels(reason="priority_evict").inc()
        victim.error = AdmissionShed(
            f"shed by a priority-{priority} request under overload "
            f"(this request: priority {victim.priority})",
            reason="priority_evict",
        )
        victim.finish()
        return True

    def _resume_args(
        self,
        ids: list[int],
        max_new_tokens: int,
        resume_tokens: list[int] | None,
    ) -> tuple[list[int], int, int]:
        """Fold a gateway mid-stream-failover resume prefix into the
        admission arguments: the committed tokens become part of the
        prompt (suffix-prefilled, or covered by a KV-span/host-tier hit)
        and the generation budget shrinks by what was already emitted, so
        the stream's TOTAL length is what the original request asked
        for. Returns ``(ids, max_new_tokens, resume_count)``."""
        if not resume_tokens:
            return list(ids), max_new_tokens, 0
        resume = len(resume_tokens)
        if max_new_tokens - resume < 1:
            raise ValueError(
                f"resume prefix ({resume} tokens) leaves no generation "
                f"budget (max_new_tokens={max_new_tokens})"
            )
        if self.eos_id in resume_tokens:
            raise ValueError(
                "resume prefix contains EOS — the stream already finished"
            )
        return (
            list(ids) + [int(t) for t in resume_tokens],
            max_new_tokens - resume,
            resume,
        )

    def submit(
        self,
        ids: list[int],
        *,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        timeout_s: float = 300.0,
        deadline: float | None = None,
        priority: int = 0,
        trace: Any = None,
        kv_span: PreparedKVSpan | None = None,
        session: str | None = None,
        resume_tokens: list[int] | None = None,
        seed: int | None = None,
    ) -> list[int]:
        """``deadline`` (absolute ``time.monotonic()``) is the end-to-end
        budget; ``timeout_s`` is the legacy knob and becomes the deadline
        when none is given — one clock governs queue wait AND decode.
        ``trace`` (a Span or parsed TraceContext) parents the engine-stage
        spans; None (warmup, untraced callers) records nothing.
        ``kv_span`` (a ``prepare_kv_span`` result for these exact ids)
        admits by implanting the peer-prefilled span — this engine never
        computes a prefill chunk for the request. ``session`` keys the
        host-RAM KV tier when it is enabled. ``resume_tokens`` (the
        mid-stream failover contract) extends the prompt with already-
        committed generated tokens and shrinks the budget to match; only
        tokens PAST the committed prefix are returned/streamed. ``seed``
        pins per-row position-folded sampling (see ``_seeded_sample``)."""
        if deadline is None:
            deadline = time.monotonic() + timeout_s
        ids, max_new_tokens, resume = self._resume_args(
            ids, max_new_tokens, resume_tokens
        )
        req = self._enqueue(
            ids, max_new_tokens, temperature, live=False,
            deadline=deadline, priority=priority, trace=trace,
            kv_inject=kv_span, session=session, resume=resume, seed=seed,
        )
        if not req.done.wait(max(0.0, deadline - time.monotonic())):
            # hand the row back: a timed-out caller must not leave its
            # row decoding tokens nobody will read
            req.cancelled.set()
            self._work.set()
            DEADLINE_EXPIRED.labels(stage="wait").inc()
            raise DeadlineExceeded("generation timed out", stage="wait")
        if req.error is not None:
            raise req.error
        return req.tokens

    def stream(
        self,
        ids: list[int],
        *,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        timeout_s: float = 300.0,
        deadline: float | None = None,
        priority: int = 0,
        trace: Any = None,
        kv_span: PreparedKVSpan | None = None,
        session: str | None = None,
        resume_tokens: list[int] | None = None,
        seed: int | None = None,
    ):
        """Yields lists of new tokens as decode chunks complete — the
        streaming data path (KServe v2 generate_stream analog).
        ``kv_span``/``session``/``resume_tokens``/``seed``: same contract
        as :meth:`submit` — a resumed stream yields only tokens past the
        committed prefix.

        Every wait is charged against ONE monotonic deadline: the old
        per-item ``get(timeout=timeout_s)`` granted the full budget per
        chunk, so a slow stream could overrun it by tokens × timeout."""
        if deadline is None:
            deadline = time.monotonic() + timeout_s
        ids, max_new_tokens, resume = self._resume_args(
            ids, max_new_tokens, resume_tokens
        )
        req = self._enqueue(
            ids, max_new_tokens, temperature, live=True,
            deadline=deadline, priority=priority, trace=trace,
            kv_inject=kv_span, session=session, resume=resume, seed=seed,
        )
        try:
            while True:
                remaining = deadline - time.monotonic()
                try:
                    if remaining <= 0:
                        raise queue.Empty
                    item = req.live.get(timeout=remaining)
                except queue.Empty:
                    DEADLINE_EXPIRED.labels(stage="wait").inc()
                    raise DeadlineExceeded(
                        "generation timed out", stage="wait"
                    ) from None
                if item is None:
                    break
                yield item
            if req.error is not None:
                raise req.error
        finally:
            # generator closed early (client disconnect) → release the row
            if not req.done.is_set():
                req.cancelled.set()
                self._work.set()

    def prefill_span(
        self,
        ids: list[int],
        *,
        temperature: float = 0.0,
        timeout_s: float = 120.0,
        deadline: float | None = None,
        trace: Any = None,
        seed: int | None = None,
    ) -> tuple[dict, dict]:
        """The prefill-pool half of disaggregated serving: run ONLY the
        (chunked) prefill of ``ids`` and return ``(tree, meta)`` — the
        finished KV span as host arrays in the prefix-entry format
        (ceil-16 window; positions past the prompt hold junk the decode
        side masks or overwrites before ever attending) plus the meta the
        decode replica needs (``real_len``, ``first_tok``, ``valid``).
        The row retires the moment the span is extracted: this engine
        never decodes the request, so ``prefill_pieces`` is the only work
        counter a pure prefill replica ever moves."""
        n16 = -(-len(ids) // 16) * 16
        # the generation budget is a LAYOUT reservation only — it sizes
        # the page allocation so the whole ceil-16 extract window is
        # backed by real pages; no decode chunk ever runs against it
        budget = max(1, n16 - len(ids) + 1)
        if deadline is None:
            deadline = time.monotonic() + timeout_s
        req = self._enqueue(
            list(ids), budget, temperature, live=False,
            deadline=deadline, trace=trace, want_kv_span=True, seed=seed,
        )
        if not req.done.wait(max(0.0, deadline - time.monotonic())):
            req.cancelled.set()
            self._work.set()
            DEADLINE_EXPIRED.labels(stage="wait").inc()
            raise DeadlineExceeded("prefill-span timed out", stage="wait")
        if req.error is not None:
            raise req.error
        if req.kv_span is None:
            raise RuntimeError("prefill-span request retired before extract")
        tree = {
            name: {
                which: np.asarray(arr)  # kft: noqa[jax-sync] — span-export D2H runs on the caller's HTTP-executor thread, never the scheduler loop
                for which, arr in lc.items()
            }
            for name, lc in req.kv_span.items()
        }
        return tree, dict(req.kv_span_meta)

    def _bucket(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(
            f"prompt length {n} exceeds largest prefill bucket "
            f"{self.prefill_buckets[-1]}"
        )

    def _admit_all(self) -> None:
        # cancelled and deadline-expired mid-generation rows free up before
        # admission looks for space — a disconnected client must not hold a
        # row, and a row past its budget must stop costing decode steps.
        # This runs at the top of every loop iteration, i.e. exactly the
        # epoch seam: _finish dirties the carry, the next chunk's carry is
        # merged with the retired row inactive, and the in-flight chunk's
        # results for it are masked out when it drains.
        now = time.monotonic()
        for row in range(self.max_batch):
            req = self._slots[row]
            if req is None:
                continue
            # deadline before cancellation: a timed-out caller sets BOTH
            # (cancel reclaims the row), and the retirement must be
            # attributed to the deadline, not to a client walk-away
            if req.deadline is not None and now > req.deadline:
                self.stats["deadline_expired_decoding"] += 1
                DEADLINE_EXPIRED.labels(stage="decoding").inc()
                req.error = DeadlineExceeded(
                    "deadline expired mid-decode", stage="decoding"
                )
                self._finish(row)
            elif req.cancelled.is_set():
                self._finish(row)
        while True:
            free = [i for i, s in enumerate(self._slots) if s is None]
            if not free:
                return
            if self._held is not None:
                req, self._held = self._held, None
            else:
                try:
                    req = self._pending.get_nowait()
                except queue.Empty:
                    return
            if req.done.is_set():
                continue  # priority-evicted while queued: already failed
            if req.deadline is not None and time.monotonic() > req.deadline:
                # retired from the queue before ever costing a decode slot
                # (checked before cancellation: a timed-out caller sets
                # both, and the deadline is the cause)
                self.stats["deadline_expired_queued"] += 1
                DEADLINE_EXPIRED.labels(stage="queued").inc()
                req.error = DeadlineExceeded(
                    "deadline expired while queued", stage="queued"
                )
                req.finish()
                continue
            if req.cancelled.is_set():
                req.finish()  # consumer already gone: never admit
                continue
            if not self.pager.can_alloc(
                self.pager.pages_for(len(req.ids) + req.max_new_tokens)
            ):
                # page backpressure: hold THIS request (FIFO — nothing
                # admits past it) until completions free pages
                self._held = req
                return
            row = free[0]
            try:
                self._admit(req, row)
            except ValueError as e:  # bad request: fail it, keep serving
                req.error = e
                req.finish()
            # anything else (device error mid-donated-call) propagates to
            # the fatal path: self.cache may now hold DELETED buffers, so
            # "keep serving" would fail every later call confusingly

    def _lookup_prefix(self, ids: list[int]):
        """Longest stored prefix strictly shorter than the prompt (at least
        one token must remain to prefill for the first-token logits).
        Keys are exact 16-multiples, so only the prompt's own descending
        16-multiples need O(1) dict probes — no scan over entries."""
        if self._prefix_cache is None:
            return None
        top = (len(ids) - 1) // 16 * 16
        with self._prefix_lock:
            if self._prefix_lens_sorted is None:
                # memoized: store/evict invalidate, so the hot admission
                # path pays the O(L log L) sort only after the SET changes
                self._prefix_lens_sorted = sorted(
                    self._prefix_lens, reverse=True
                )
            # probe only lengths ACTUALLY stored (descending): a long-
            # prompt miss costs len(stored-lengths) tuple builds, not
            # len(prompt)/16
            for n16 in self._prefix_lens_sorted:
                if n16 > top:
                    continue
                key = tuple(ids[:n16])
                entry = self._prefix_cache.get(key)
                if entry is not None:
                    self._prefix_cache.move_to_end(key)
                    return key, entry
        return None

    def _store_prefix(self, ids: list[int], row: int) -> None:
        """Donate row ``row``'s KV for ids[:n16] — the row's first n16 slots
        must hold contiguous REAL tokens (true after a full prefill, and
        after a hit's implant+suffix since real tokens stay contiguous)."""
        n16 = (len(ids) // 16) * 16
        if n16 < 16 or (
            self._prefix_cache_tokens is not None
            and n16 > self._prefix_cache_tokens
        ):
            return
        key = tuple(ids[:n16])
        with self._prefix_lock:
            if key in self._prefix_cache:
                self._prefix_cache.move_to_end(key)
                return
            self._insert_prefix_locked(key, self._extract_prefix(row, n16))

    def _insert_prefix_locked(self, key: tuple, entry: dict) -> None:
        """Insert one entry + LRU-evict to bounds. Caller holds
        ``_prefix_lock``; shared by the store path and the peer import."""
        n16 = len(key)
        self._prefix_cache[key] = entry
        if n16 not in self._prefix_lens:
            self._prefix_lens_sorted = None  # length set changed
        self._prefix_lens[n16] = self._prefix_lens.get(n16, 0) + 1
        self._prefix_tokens_stored += n16
        # evict LRU until within BOTH bounds: entry count and (when set)
        # total stored tokens — entry count alone lets HBM scale with
        # prefix length (one 1024-token entry can be hundreds of MB)
        while len(self._prefix_cache) > self._prefix_cache_entries or (
            self._prefix_cache_tokens is not None
            and self._prefix_tokens_stored > self._prefix_cache_tokens
            and len(self._prefix_cache) > 1
        ):
            old_key, _ = self._prefix_cache.popitem(last=False)
            n = len(old_key)
            self._prefix_tokens_stored -= n
            self._prefix_lens[n] -= 1
            if not self._prefix_lens[n]:
                del self._prefix_lens[n]
                self._prefix_lens_sorted = None  # length set changed

    def _admit(self, req: _Request, row: int) -> None:
        """Claim a row: implant any cached prefix, lay out the prefill
        region, and process the FIRST piece. Long prompts (chunked prefill)
        leave the row in 'prefilling' state — subsequent pieces interleave
        with decode chunks so admissions never stall in-flight rows."""
        if req.kv_inject is not None:
            self._admit_injected(req, row)
            return
        base, rest = 0, req.ids
        hit = self._lookup_prefix(req.ids)
        if hit is None and req.session and self.host_kv_tier is not None:
            # host-tier swap-in: the session's previous turn parked its
            # span here — it re-enters through the prefix-implant path
            # (same machinery, different store) and continues
            # byte-identically
            hit = self._take_swapped(req)
        # claim pages FIRST: _admit_all verified availability; implant
        # needs the table row populated
        self.pager.alloc(
            row, self.pager.pages_for(len(req.ids) + req.max_new_tokens)
        )
        if hit is not None:
            key, stored = hit
            base, rest = len(key), req.ids[len(key):]
            # suffixes bucket at the 16-token prefix quantum, NOT the full
            # prefill buckets — padding a 4-token tail to a 128 bucket
            # would waste a whole piece on pad slots
            C = self.prefill_chunk or ((len(rest) + 15) // 16) * 16
            self._implant_paged(stored, row, base)
            self.stats["prefix_hits"] += 1
            self.stats["prefix_tokens_reused"] += base
        else:
            C = self.prefill_chunk or self._bucket(len(rest))
        n_pieces = -(-len(rest) // C)
        req.row = row
        self._slots[row] = req
        self.real_len[row] = len(req.ids)
        if self.spec_k:
            # history mirror: the prompt is host data — seeding it here
            # costs nothing and the next carry upload ships it
            self.hist_host[row, :] = self.pad_id
            self.hist_host[row, : len(req.ids)] = req.ids
        self.gen_count[row] = 0
        self.budget[row] = req.max_new_tokens
        self.temp[row] = req.temperature
        self.seeds[row] = -1 if req.seed is None else req.seed
        self.stats["admitted"] += 1
        self.stats["max_concurrent"] = max(
            self.stats["max_concurrent"], sum(s is not None for s in self._slots)
        )
        self.stats["kv_pages_used_peak"] = max(
            self.stats["kv_pages_used_peak"], self.pager.used_pages
        )
        if req.qspan is not None:
            req.qspan.end()
            req.qspan = None
        if req.espan is not None:
            req.pspan = (
                TRACER.span("prefill", parent=req.espan)
                .set_attr("row", row)
                .set_attr("prefix_hit", base > 0)
                .set_attr("prefix_tokens_reused", base)
                .set_attr("pieces", n_pieces)
                .set_attr("prompt_tokens", len(req.ids))
                .set_attr("padded_tokens", n_pieces * C)
            )
        self._prefilling[row] = {
            "req": req, "rest": rest, "base": base, "C": C,
            "n_pieces": n_pieces, "piece": 0,
        }
        # admission epoch: the per-row mirrors and the block table changed —
        # the next dispatch must rebuild the carry, this row from the host
        self._carry_dirty = True
        self._carry_edit[row] = True
        if n_pieces == 1:
            # single-piece prompts dispatch their piece now (no interleaving
            # to gain); multi-piece rows take ONE piece per loop iteration
            # via _advance_prefills so decode chunks run between pieces
            self._advance_prefill(row)

    def _admit_injected(self, req: _Request, row: int) -> None:
        """Admit a peer-prefilled request: implant its shipped KV span
        and activate the row directly — the disaggregation invariant is
        that this engine NEVER computes a prefill chunk for it (on a pure
        decode-pool replica ``prefill_pieces`` stays zero). The span's
        first sampled token rides the meta, so the request starts exactly
        where the prefill replica left it: decode overwrites the span's
        junk [real_len, n16) with real KV before any query position
        reaches it."""
        span = req.kv_inject
        tree, meta, n16 = span.tree, span.meta, span.n16
        # claim pages FIRST (availability verified by _admit_all); the
        # allocation covers len + max_new >= the implant window
        self.pager.alloc(
            row, self.pager.pages_for(len(req.ids) + req.max_new_tokens)
        )
        self._implant_paged(tree, row, n16)
        req.row = row
        self._slots[row] = req
        self._carry_edit[row] = True
        self.real_len[row] = len(req.ids)
        if self.spec_k:
            self.hist_host[row, :] = self.pad_id
            self.hist_host[row, : len(req.ids)] = req.ids
        self.gen_count[row] = 0
        self.budget[row] = req.max_new_tokens
        self.temp[row] = req.temperature
        self.seeds[row] = -1 if req.seed is None else req.seed
        self.stats["admitted"] += 1
        self.stats["kv_injected"] += 1
        self.stats["max_concurrent"] = max(
            self.stats["max_concurrent"],
            sum(s is not None for s in self._slots),
        )
        self.stats["kv_pages_used_peak"] = max(
            self.stats["kv_pages_used_peak"], self.pager.used_pages
        )
        if req.qspan is not None:
            req.qspan.end()
            req.qspan = None
        if req.espan is not None:
            req.espan.set_attr("kv_injected", True)
        tok = int(meta["first_tok"])
        if bool(meta["valid"]):
            req.push([tok])
            if self.spec_k:
                self.hist_host[row, len(req.ids)] = tok
        self.last_tok[row] = tok
        finished = (not bool(meta["valid"])) or req.max_new_tokens <= 1
        if finished:
            self._finish(row)
        else:
            self.active[row] = True
            self.gen_count[row] = 1
            self._carry_dirty = True

    def _take_swapped(self, req: _Request):
        """Consume the host tier's stored span for the request's session
        (when its tokens prefix the new prompt), decode it through the
        npz codec, and return ``(key, jnp tree)`` in _lookup_prefix's
        format — or None (miss, diverged prompt, corrupt or incompatible
        blob: all degrade to a normal full prefill)."""
        blob = self.host_kv_tier.take(req.session, req.ids)
        if blob is None:
            return None
        try:
            entries, _ = decode_kv_entries(blob)
            key, tree = entries[0]
        except Exception:  # noqa: BLE001 — a corrupt blob is a miss
            return None
        n16 = len(key)
        if n16 < 16 or n16 % 16 or self._span_reject(tree, n16) is not None:
            return None
        jtree = {
            name: {which: jnp.asarray(arr) for which, arr in lc.items()}
            for name, lc in tree.items()
        }
        self.stats["kv_offload_in"] += 1
        return tuple(key), jtree

    def _advance_prefill(self, row: int) -> None:
        """Dispatch ONE prefill piece for a prefilling row and return
        without waiting for it. The final piece's first token stays a
        device handle in ``_firsts``: the epoch's merge activates the row
        with it on the device, and ``_take_firsts`` reads it once the
        chunk that decodes the row is queued."""
        with self._phase("prefill_dispatch"):
            st = self._prefilling[row]
            req, rest, base, C = st["req"], st["rest"], st["base"], st["C"]
            i = st["piece"]
            final = i == st["n_pieces"] - 1
            piece_ids = rest[i * C: (i + 1) * C]
            piece = np.full((1, C), self.pad_id, np.int32)
            piece[0, : len(piece_ids)] = piece_ids
            self._rng, sub = jax.random.split(self._rng)
            # the sampled token's absolute position: one past this piece's
            # last prompt token (only the FINAL piece's sample is kept, where
            # this equals len(req.ids) — the first generated position)
            seed = -1 if req.seed is None else req.seed
            pos = base + i * C + len(piece_ids)
            pages_w = self._pages_w(base + i * C + C)
            with self._mesh_scope():
                flash_read = paged_flash_read(self.cfg, C)
                self.cache, tok, valid, qerr, *moe = self._suffix_prefill(
                    self.params,
                    self.cache,
                    jnp.asarray(piece),
                    jnp.asarray([len(piece_ids)], np.int32),
                    base + i * C,
                    jnp.asarray(
                        self.pager.table[row : row + 1, :pages_w].copy()
                    ),
                    jnp.float32(req.temperature),
                    seed,
                    pos,
                    sub,
                    seeded=req.seed is not None,
                )
        if self.kv_quant == "int8":
            self._qerrs.append(qerr)  # read with the first tokens
        self.stats["prefill_pieces"] += 1
        self.stats["prefill_pieces_flash_read"] += flash_read
        self.stats["prefill_tokens"] += len(piece_ids)
        self.stats["prefill_padded_tokens"] += C
        st["piece"] = i + 1
        # device handles: read with the final piece's sample
        st.setdefault("moe", []).extend(moe)
        if not final:
            return  # tok is a throwaway sample from a non-final position
        del self._prefilling[row]
        # the row's mirrors as the merged carry holds them before the token
        # is read: one token generated, live if it is to decode at all (an
        # EOS first token retires it in _take_firsts)
        self.gen_count[row] = 1
        self.active[row] = not req.want_kv_span and req.max_new_tokens > 1
        self._carry_dirty = True
        self._carry_edit[row] = True
        self._filler = (tok, valid)
        self._firsts.append((row, req, tok, valid, st["moe"]))
        if req.pspan is not None and not st["moe"]:
            req.pspan.end()  # admission to the last piece's dispatch
            req.pspan = None

    def _take_firsts(self) -> None:
        """Read the first tokens of the final pieces dispatched so far —
        in an epoch after its chunk is queued, so the reads wait only on
        programs ahead of that chunk — and finish their admissions: push
        the token, settle the row's mirrors, retire a one-token
        completion (the carry already gates it), store the prompt's
        prefix, export a KV span."""
        if self._qerrs:
            with self._phase("prefill_wait"):
                errs = [(float(q[0]), float(q[1])) for q in self._qerrs]
            self._qerrs = []
            for e, d in errs:
                if d > 0:
                    self._ewma("kv_quant_error", e / d)
        firsts, self._firsts = self._firsts, []
        for row, req, tok, valid, moe in firsts:
            if self._slots[row] is not req:
                continue  # failed before its first token was read (stop)
            self._take_first(row, req, tok, valid, moe)

    def _take_first(self, row, req, tok, valid, moe) -> None:
        with self._phase("prefill_wait"):
            routed = self._count_moe(moe, "prefill") if moe else None
            tok, valid = int(tok), bool(valid)
        if req.pspan is not None:
            # a routed model's prefill span ends where its counts are read
            if routed is not None:
                req.pspan.set_attr("assignments", routed["assignments"])
                req.pspan.set_attr(
                    "experts_touched", routed["experts_touched"]
                )
            req.pspan.end()
            req.pspan = None
        if self._prefix_cache is not None:
            self._store_prefix(req.ids, row)
        if req.want_kv_span:
            # disaggregated prefill: extract the finished span (ceil-16
            # window) and retire the row WITHOUT activating — a prefill
            # replica never decodes this request, and no token is pushed
            # (the first sampled token travels in the meta instead, so
            # TTFT is observed once, on the decode side)
            n16 = -(-len(req.ids) // 16) * 16
            req.kv_span = self._extract_prefix(row, n16)
            req.kv_span_meta = {
                "real_len": len(req.ids),
                "first_tok": tok,
                "valid": valid,
            }
            self.stats["kv_spans_exported"] += 1
            self._finish(row, carry_stale=False)
            return
        if valid:
            req.push([tok])
            if self.spec_k:
                self.hist_host[row, len(req.ids)] = tok
        self.last_tok[row] = tok
        # one-token completions (eos first, or budget 1) finish here; the
        # merge left them inactive on the device
        if (not valid) or req.max_new_tokens <= 1:
            self._finish(row, carry_stale=False)

    def _advance_prefills(self) -> None:
        for row in list(self._prefilling):
            req = self._prefilling[row]["req"]
            if req.cancelled.is_set():
                self._finish(row)
                continue
            self._advance_prefill(row)

    def _finish(self, row: int, *, carry_stale: bool = True) -> None:
        req = self._slots[row]
        self._slots[row] = None
        self.active[row] = False
        # freed row no longer forces the seeded chunk-program variant
        self.seeds[row] = -1
        was_prefilling = self._prefilling.pop(row, None) is not None
        if (
            req is not None
            and self.host_kv_tier is not None
            and req.session
            and req.error is None
            and not req.want_kv_span
            and not was_prefilling  # mid-prefill rows: KV incomplete
        ):
            # swap-out must extract BEFORE the pages free (the block
            # table row is still this request's)
            self._swap_out(req, row)
        self.pager.free(row)
        # ``carry_stale=False`` is the drain's EOS/budget retirement: the
        # device carry already gates the row in-graph (active=False after
        # EOS; gen_count==budget masks it live=False), so no re-upload is
        # needed and steady-state completions stay epoch-free. Host-only
        # retirements (cancellation, failed admission) leave the device
        # thinking the row is live → dirty the carry.
        if carry_stale:
            self._carry_dirty = True
        if req is not None:
            # count BEFORE done.set(): callers may read/reset stats the
            # moment their submit returns (warmup does)
            self.stats["completed"] += 1
            req.finish()

    def _swap_out(self, req: _Request, row: int) -> None:
        """Queue a finished sessioned row's KV span for the host tier.
        KV is written for the first ``real_len + emitted - 1`` context
        positions (the last emitted token's KV is never computed). The
        extract here is device handles (async); the D2H + encode runs on
        the offload worker thread."""
        ctx_tokens = list(req.ids) + list(req.tokens)
        written = len(req.ids) + max(0, len(req.tokens) - 1)
        n16 = (min(written, self.max_seq) // 16) * 16
        if n16 < 16:
            return
        try:
            tree = self._extract_prefix(row, n16)
        except Exception:  # noqa: BLE001 — swap-out is best-effort: a
            return         # failed extract just means a re-prefill later
        self._offload_q.put((req.session, tuple(ctx_tokens[:n16]), tree))

    def _offload_loop(self) -> None:
        """Offload worker: the swap-out D2H sync + npz encode + tier
        insert run HERE, never on the scheduler thread — a host-tier
        swap-out must not stall decode dispatch. Items are (session,
        key_tokens, device_tree); a threading.Event is a flush barrier
        (tests/drain); None exits."""
        from kubeflow_tpu.serve.kv_codec import encode_kv_entries

        while True:
            item = self._offload_q.get()
            if item is None:
                return
            if isinstance(item, threading.Event):
                item.set()
                continue
            session, key, tree = item
            try:
                host = {
                    name: {
                        which: np.asarray(arr)  # kft: noqa[jax-sync] — host-tier swap-out D2H runs on the offload worker thread, never the scheduler loop
                        for which, arr in lc.items()
                    }
                    for name, lc in tree.items()
                }
                blob = encode_kv_entries([(key, host)])
                if self.host_kv_tier.put(session, key, blob):
                    self.stats["kv_offload_out"] += 1
            except Exception:  # noqa: BLE001 — swap-out is best-effort;
                pass           # the session re-prefills on its next turn

    def flush_offload(self, timeout_s: float = 10.0) -> bool:
        """Block until every swap-out queued so far has landed in the
        host tier (tests and drain hooks; production never waits)."""
        if self._offload_q is None or self._offload_thread is None:
            return True
        done = threading.Event()
        self._offload_q.put(done)
        return done.wait(timeout_s)

    @contextlib.contextmanager
    def _phase(self, name: str):
        """One scheduler phase, two outputs at the same boundary: a host
        span ``engine.<name>`` on the profiler's clock (beside the device's
        events in a ``POST /profile`` capture; free while no profile runs),
        and ``stats["sched_<name>_s"]`` / ``_n`` — the phase's self seconds
        (its own minus those of phases nested in it) and entries, exported
        by ``/metrics`` like every ``stats`` key. Scheduler thread only. The
        annotation takes no keyword arguments: they would be formatted into
        the event's name and split one phase into many."""
        nested = self._phase_nested
        nested.append(0.0)
        with jax.profiler.TraceAnnotation("engine." + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                took = time.perf_counter() - t0
                inner = nested.pop()
                if nested:
                    nested[-1] += took
                self.stats[f"sched_{name}_s"] += took - inner
                self.stats[f"sched_{name}_n"] += 1

    def _loop(self) -> None:
        try:
            self._loop_inner()
        except Exception as e:  # noqa: BLE001
            # the scheduler thread must NEVER die silently: every in-flight
            # and queued request gets the real error now, and later submits
            # fail fast instead of hanging to their timeout
            self._fatal = e
            for row in range(self.max_batch):
                req = self._slots[row]
                if req is not None:
                    req.error = e
                    self._slots[row] = None
                    req.finish()
            if self._held is not None:
                self._held.error = e
                self._held.finish()
                self._held = None
            while True:
                try:
                    req = self._pending.get_nowait()
                except queue.Empty:
                    break
                req.error = e
                req.finish()

    def _loop_inner(self) -> None:
        pending: _PendingChunk | None = None
        while not self._stop.is_set():
            t0 = time.perf_counter()
            pending = self._loop_once(pending)
            self.stats["sched_loop_s"] += time.perf_counter() - t0

    def _loop_once(self, pending: _PendingChunk | None) -> _PendingChunk | None:
        """One scheduler iteration; takes and returns the chunk in flight.

        An iteration that admits, activates or retires rows is an epoch,
        and it keeps the pipeline full: every admitted row's pieces are
        dispatched back to back, the next chunk's carry is merged on the
        device from the chunk in flight (``_upload_carry``), that chunk
        is dispatched, and only then are the first tokens read and the
        chunk in flight drained. With nothing in flight the first tokens
        are read at once and the carry is built from current mirrors."""
        # watchdog heartbeat: stale while work exists ⇒ the loop is
        # wedged inside a device call (or a chaos hook)
        self._beat = time.monotonic()
        with self._phase("admit"):
            self._admit_all()
            self._advance_prefills()  # one piece per prefilling row
            if pending is None:
                self._take_firsts()  # nothing in flight to hide them
        if not self.active.any():
            if pending is not None:
                # burst tail: the speculative chunk outlived its rows
                # (host mirrors may also lag it by one chunk) — drain
                # it, then re-evaluate
                self._drain_chunk(pending)
                return None
            if self._prefilling:
                return None  # keep advancing pieces, don't park
            # idle: park until submit/stream-cancel/stop sets _work —
            # every waker does, so the long timeout is only a
            # belt-and-braces sweep, never a 20 Hz poll. Clearing after
            # the wait cannot lose work: _admit_all re-polls the queue
            # at the top of the next iteration.
            self._last_dispatch = None
            self.stats["idle_wakes"] += 1
            with self._phase("park"):
                self._work.wait(_IDLE_PARK_S)
            self._work.clear()
            return None
        if self.pipeline_depth == 0:
            # inline parity/debug path: per-chunk H2D upload and an
            # immediate D2H drain — the pre-pipeline hot loop, kept
            # selectable so pipelined parity is provable seed-for-seed
            with self._phase("carry_upload"):
                self._count_epoch(drained=True)
                self._upload_carry()
            with self._phase("chunk_dispatch"):
                nxt = self._dispatch_chunk()
            self._drain_chunk(nxt)
            return None
        if pending is not None and not self._firsts and self._all_may_retire():
            # end-of-burst: every active row can exhaust its budget
            # inside the in-flight chunk, so a speculative dispatch
            # would likely decode only dead rows — drain first instead
            # and let the retirements land (EOS tails still cost at
            # most one dead chunk; budgets are host-knowable, EOS
            # isn't). A row whose first token is pending needs a chunk.
            self._drain_chunk(pending)
            return None
        if self._carry_dirty:
            # the epoch: with a chunk in flight the carry is merged from
            # its outputs, which the host mirrors lag by up to a chunk
            with self._phase("carry_upload"):
                self._count_epoch(drained=pending is None)
                self._upload_carry(
                    lag=0 if pending is None else self._chunk_span
                )
        # one-chunk-ahead: dispatch N+1 on the device carry BEFORE reading
        # the epoch's first tokens and draining N, so N's token D2H + host
        # postprocess overlap N+1's device compute
        with self._phase("chunk_dispatch"):
            nxt = self._dispatch_chunk()
        if self._firsts:
            with self._phase("admit"):
                self._take_firsts()
        if pending is not None:
            self._drain_chunk(pending)
        return nxt

    def _count_epoch(self, *, drained: bool) -> None:
        """Count a carry rebuild that a host edit caused, and whether the
        pipeline was empty for it (``stats["epochs"]``,
        ``stats["epoch_drains"]``)."""
        if self._carry_dirty:
            self.stats["epochs"] += 1
            self.stats["epoch_drains"] += int(drained)

    # -- pipelined decode: carry upload / dispatch / drain ------------------- #

    @property
    def _chunk_span(self) -> int:
        """Max tokens one chunk can advance a row: chunk_steps classic
        steps, times up-to-(K+1) emitted per step under speculation."""
        return self.chunk_steps * (self.spec_k + 1)

    def _all_may_retire(self) -> bool:
        """True when every host-visible active row could exhaust its token
        budget within ONE more chunk. The host mirrors lag the in-flight
        chunk by at most one chunk's span, so remaining ≤ span means the
        undrained chunk may already retire the whole batch."""
        act = self.active
        if not act.any():
            return True
        remaining = (self.budget - self.gen_count)[act]
        return bool((remaining <= self._chunk_span).all())

    def _ewma(self, key: str, value: float, alpha: float = 0.2) -> None:
        cur = self.overlap[key]
        self.overlap[key] = value if cur == 0.0 else (
            (1.0 - alpha) * cur + alpha * value
        )

    def _upload_carry(self, lag: int = 0) -> None:
        """Build the carry of the next dispatch — the ONE H2D an epoch
        pays: the fields only the host changes (lengths, budgets,
        temperatures, seeds, the block table, under speculation the
        history) from the mirrors, and the decode state (last token,
        generation count, liveness) by the merge program (``_merge``)
        from the carry a chunk left, row by row: a row the host says
        decodes and did not edit keeps the device's values (the chunk in
        flight may have moved it on), a row whose final prefill piece is
        pending takes that piece's token while it is still a device
        handle, every other row takes the host's. ``lag``: how far the
        mirrors of the kept rows may trail the device — a chunk's span
        when one is in flight, which the page window must cover.

        Every mirror is ``.copy()``-snapshotted first: on the CPU backend
        ``jnp.asarray`` of an aligned numpy buffer is ZERO-COPY, so the
        "device" carry would alias the live mirrors and later in-place
        host edits (prefill activation, drain refresh) would retroactively
        rewrite what an in-flight chunk reads — an interleaving-dependent
        wrong-token/lost-row race (observed as chunked-prefill rows
        truncating to their first token under churn)."""
        prev = self._carry
        first = np.zeros((self.max_batch,), bool)
        if self._filler is None:  # no piece yet: an engine of implants
            self._filler = (
                jnp.asarray(np.zeros((), np.int32)),
                jnp.asarray(np.zeros((), np.bool_)),
            )
        toks, valids = (
            [self._filler[0]] * self.max_batch,
            [self._filler[1]] * self.max_batch,
        )
        for row, _, tok, valid, _ in self._firsts:
            first[row], toks[row], valids[row] = True, tok, valid
        keep = self.active & ~self._carry_edit & ~first & (prev is not None)
        host = np.stack([
            np.where(keep, _KEEP, np.where(first, _FIRST, _HOST)),
            self.last_tok, self.gen_count, self.active,
        ]).astype(np.int32)
        c: dict[str, Any] = {
            "real_len": jnp.asarray(self.real_len.copy()),
            "budget": jnp.asarray(self.budget.copy()),
            "temp": jnp.asarray(self.temp.copy()),
            "seed": jnp.asarray(self.seeds.copy()),
        }
        if prev is None:
            prev = {
                "last_tok": jnp.asarray(self.last_tok.copy()),
                "gen_count": jnp.asarray(self.gen_count.copy()),
                "active": jnp.asarray(self.active.copy()),
            }
            if self.spec_k:
                prev["hist"] = jnp.asarray(self.hist_host.copy())
        # the device history is rewritten in-graph chunk→chunk; an epoch
        # takes the rows it edits from the host mirror — one small int32
        # H2D per epoch
        hist = (
            (prev["hist"], jnp.asarray(self.hist_host.copy()), c["real_len"])
            if self.spec_k else ()
        )
        with self._mesh_scope():
            c["last_tok"], c["gen_count"], c["active"], *merged = self._merge(
                prev["last_tok"], prev["gen_count"], prev["active"],
                jnp.asarray(host), tuple(toks), tuple(valids), *hist,
            )
        if self.spec_k:
            (c["hist"],) = merged
        self._carry_edit[:] = False
        # host-side twin of c["seed"]: picks the chunk-program variant
        # without a device sync (static `seeded` jit specialization)
        self._carry_seeded = bool((self.seeds >= 0).any())
        act = self.active
        if act.any():
            reach = self.real_len + self.gen_count
            self._carry_h0 = int(reach[act].max()) + lag
            self._carry_hcap = int((self.real_len + self.budget)[act].max())
        else:
            self._carry_h0 = self._carry_hcap = 0
        w = self._pages_w(
            max(min(self._carry_h0 + self._chunk_span,
                    self._carry_hcap), 1)
        )
        # memoized device mirror: unchanged table + same width = no H2D
        c["table"] = self.pager.device_table(w)
        self._carry_pages_w = w
        self._carry = c
        self._carry_dirty = False
        self._carry_chunks = 0
        self.overlap["carry_uploads"] += 1

    def _dispatch_chunk(self) -> _PendingChunk:
        """Dispatch one decode chunk on the device carry (async — returns
        device handles immediately) and thread the returned per-row arrays
        into the carry for the next dispatch: the steady state performs
        zero per-chunk H2D of per-row arrays."""
        hook = self._fault_hooks.get("pre_chunk")
        if hook is not None:
            # chaos seam: WedgeEngine blocks here (the watchdog's wedge
            # signal), SlowDecode sleeps here (inflated chunk latency)
            hook(self)
        now = time.perf_counter()
        if self._last_dispatch is not None:
            self._ewma("decode_gap_ms", (now - self._last_dispatch) * 1e3)
        self._last_dispatch = now
        self._ewma(
            "slot_occupancy",
            sum(s is not None for s in self._slots) / self.max_batch,
        )
        self._rng, sub = jax.random.split(self._rng)
        c = self._carry
        active_in = c["active"]
        eos = prop = acc = None
        # page-horizon growth across speculative chunks: active rows
        # advance ≤ chunk_span tokens per chunk (chunk_steps × up to K+1
        # under speculation), so this bound covers every write/read this
        # chunk can reach; when it crosses a pow2 page bucket, widen the
        # device table (the host table is constant within an epoch, so
        # widening mid-flight is safe)
        horizon = min(
            self._carry_h0 + (self._carry_chunks + 1) * self._chunk_span,
            self._carry_hcap,
        )
        w = self._pages_w(max(horizon, 1))
        if w > self._carry_pages_w:
            c["table"] = self.pager.device_table(w)
            self._carry_pages_w = w
            self.overlap["carry_uploads"] += 1
        with self._mesh_scope():
            if self.spec_k:
                (
                    self.cache, c["hist"], tok, gen_count, active,
                    toks, valid, eos, prop, acc, *moe,
                ) = self._chunk(
                    self.params, self.cache, c["hist"], c["last_tok"],
                    c["real_len"], c["gen_count"], c["active"], c["budget"],
                    c["temp"], c["seed"], sub, c["table"],
                    seeded=self._carry_seeded,
                )
            else:
                (
                    self.cache, tok, gen_count, active, toks, valid, *moe
                ) = self._chunk(
                    self.params, self.cache, c["last_tok"], c["real_len"],
                    c["gen_count"], c["active"], c["budget"], c["temp"],
                    c["seed"], sub, c["table"], seeded=self._carry_seeded,
                )
        c["last_tok"], c["gen_count"], c["active"] = tok, gen_count, active
        self._carry_chunks += 1
        self.stats["chunks"] += 1
        self.stats["decode_chunks_kernel_read"] += self.kernel_read
        # the rows' reach as the host last saw it (the drain of a chunk in
        # flight will move it on by up to a chunk's tokens)
        reach = (self.real_len + self.gen_count)[self.active]
        pages_live = int((-(-reach // self.page_size)).sum())
        self.stats["decode_pages_live"] += pages_live
        self.stats["decode_pages_window"] += (
            self.max_batch * self._carry_pages_w
        )
        self.stats["kv_pages_held"] += self.cfg.n_layers * pages_live
        for window, layers in self._layer_windows.items():
            if window is None:
                continue
            # the pages wholly before the last token's window: those
            # below the one that holds key (reach - 1) - window + 1
            self.stats["kv_pages_dead_window"] += layers * int(
                (np.maximum(reach - window, 0) // self.page_size).sum()
            )
        if self.kernel_read:
            steps, live = self._kernel_grid()
            self.stats["decode_kernel_steps"] += steps
            self.stats["decode_kernel_steps_live"] += live
        return _PendingChunk(
            toks=toks, valid=valid, last_tok=tok, gen_count=gen_count,
            active_out=active, active_in=active_in,
            slots=list(self._slots), eos=eos, prop=prop, acc=acc,
            moe=moe[0] if moe else None, t_dispatch=time.monotonic(),
        )

    def _kernel_grid(self) -> tuple[int, int]:
        """The paged kernel's grid in one decode step, summed over the
        layers: its length and, of that, the steps that stage a page a
        row reads — from the rows' reach as the host last saw it, each
        layer's window and the table's width, through the kernel's own
        `paged_work`. A row the host holds inactive holds no page here."""
        width = self._carry_pages_w
        pos0 = self.real_len + self.gen_count - 1
        held = np.where(self.active, width, 0)
        pages = self._kernel_tile(table_pages=width).pages
        steps = live = 0
        for window, layers in self._layer_windows.items():
            work = paged_work(
                pos0, table_pages=width, page_size=self.page_size,
                span=self.spec_k + 1, window=window, pages=pages, held=held,
            )
            steps += layers * int(work.steps)
            live += layers * int(work.live)
        return steps, live

    def _drain_chunk(self, p: _PendingChunk) -> None:
        """Bring one chunk's results to the host, credit tokens to the
        requests that were resident at dispatch, lazily refresh the host
        mirrors, and retire rows that hit EOS or budget. Results of rows
        retired while the chunk was speculatively in flight are masked
        out: their tokens belong to a request that no longer owns the
        row."""
        with self._phase("drain_wait"):
            t0 = time.perf_counter()
            # decode boundary: generated tokens must reach the host to stream
            # to clients — this D2H is the product, not a stall; it runs on the
            # engine scheduler thread (never a request thread) and, pipelined,
            # overlaps the NEXT chunk's device compute
            toks, valid, act_in, last, genc, act_out = (
                np.asarray(x)  # kft: noqa[jax-sync] — sanctioned decode-boundary D2H on the scheduler thread; overlapped by the in-flight next chunk
                for x in (p.toks, p.valid, p.active_in, p.last_tok,
                          p.gen_count, p.active_out)
            )
            if self.spec_k:
                eos_pl, prop_pl, acc_pl = (
                    np.asarray(x)  # kft: noqa[jax-sync] — same sanctioned decode-boundary D2H; tiny (B, steps) planes riding the token drain
                    for x in (p.eos, p.prop, p.acc)
                )
            self._ewma("d2h_drain_ms", (time.perf_counter() - t0) * 1e3)
        with self._phase("drain_emit"):
            if p.moe is not None:
                self._count_moe([p.moe], "decode")
            chunk_prop = chunk_acc = 0
            for row in range(self.max_batch):
                req = p.slots[row]
                if req is None or not act_in[row]:
                    continue  # free or still prefilling at dispatch: no tokens
                if self._slots[row] is not req:
                    # retired (cancelled / re-admitted) while this chunk was in
                    # flight: mask its speculative results — mirrors for this
                    # row were rewritten by the host edit and must stand
                    continue
                hit_eos = False
                fresh: list[int] = []
                if self.spec_k:
                    # (steps, K+1) planes: each step's valid tokens are a
                    # PREFIX of its span (live positions are a prefix and EOS
                    # can only be the last live one) — a non-valid plane
                    # inside a step means "not emitted", only the eos flag (a
                    # LIVE EOS landed) stops the row. Walked with numpy, not
                    # a python scalar loop: B x steps x (K+1) iterations per
                    # chunk would hand back the very host time the pipeline
                    # exists to hide.
                    v, t, e = valid[row], toks[row], eos_pl[row]
                    hit_eos = bool(e.any())
                    stop_s = (
                        int(np.argmax(e)) if hit_eos else self.chunk_steps - 1
                    )
                    flat = t[: stop_s + 1][v[: stop_s + 1]]   # prefix-ordered
                    remaining = req.max_new_tokens - len(req.tokens)
                    fresh = [int(x) for x in flat[:remaining]]
                    row_prop = int(prop_pl[row].sum())
                    row_acc = int(acc_pl[row].sum())
                    self.stats["spec_proposed"] += row_prop
                    self.stats["spec_accepted"] += row_acc
                    chunk_prop += row_prop
                    chunk_acc += row_acc
                    # history mirror: drained tokens land at their token
                    # positions so the next epoch re-upload is exact
                    start = int(self.real_len[row]) + len(req.tokens)
                    self.hist_host[row, start : start + len(fresh)] = fresh
                else:
                    for j in range(self.chunk_steps):
                        if len(req.tokens) + len(fresh) >= req.max_new_tokens:
                            break
                        if not valid[row, j]:
                            hit_eos = True
                            break
                        fresh.append(int(toks[row, j]))
                req.push(fresh)
                if req.espan is not None and fresh:
                    # retroactive decode.chunk span (host ints only): stamped
                    # at dispatch, reported here so the loop never holds an
                    # open span per chunk
                    attrs: dict[str, Any] = {"row": row, "tokens": len(fresh)}
                    if self.spec_k:
                        attrs["spec_proposed"] = row_prop
                        attrs["spec_accepted"] = row_acc
                    TRACER.record_span(
                        "decode.chunk", parent=req.espan,
                        start=p.t_dispatch, end=time.monotonic(), attrs=attrs,
                    )
                # lazy mirror refresh from the drained outputs — the only place
                # host state learns device progress; per-row (not wholesale) so
                # rows edited by admit/prefill keep their newer host values
                self.last_tok[row] = last[row]
                self.gen_count[row] = genc[row]
                self.active[row] = bool(act_out[row])
                if hit_eos or len(req.tokens) >= req.max_new_tokens:
                    # device-visible retirement: the carry already gates this
                    # row in-graph, so no epoch is burned
                    self._finish(row, carry_stale=False)
        if not self.active.any():
            # the batch ran empty: whatever passes before the next dispatch
            # (idling, an admission's prefill, a program load) is no decode
            # gap. Parking resets this too, but only if the loop gets there
            # before the next request does — and one such sample seeds the
            # EWMA that estimate_admission sheds by (found on the chip: a
            # 3.4 s seed from warm-up shed a fifth of the first wave)
            self._last_dispatch = None
        if chunk_prop:
            # kft_engine_spec_acceptance: EWMA accepted/proposed ratio —
            # the live signal for whether prompt-lookup pays on this
            # replica's traffic
            self._ewma("spec_acceptance", chunk_acc / chunk_prop)

    def prefix_cache_stats(self) -> dict:
        """Prefix-cache effectiveness counters for /metrics exposition
        (kft_engine_prefix_*): cumulative hits / tokens reused plus live
        entry and stored-token occupancy, and the peer-transfer counters
        (entries imported from / exported to other replicas)."""
        return {
            "hits": self.stats["prefix_hits"],
            "tokens_reused": self.stats["prefix_tokens_reused"],
            "entries": len(self._prefix_cache or ()),
            "tokens_stored": self._prefix_tokens_stored,
            "imported": self.stats["prefix_imported"],
            "exported": self.stats["prefix_exported"],
        }

    # -- cross-replica prefix-KV transfer ----------------------------------- #

    def prefix_index(self) -> list[tuple[int, ...]]:
        """The stored prefix keys, LRU→MRU — what a peer needs to decide
        which entries the hash ring now assigns to it."""
        with self._prefix_lock:
            return list(self._prefix_cache or ())

    def export_prefix_entries(
        self, keys=None, *, limit: int | None = None
    ):
        """Host copies of stored entries for wire transfer:
        ``[(key, {layer: {"k": np, "v": np}}), ...]``. ``keys=None``
        exports everything (MRU last); ``limit`` keeps only the hottest
        (most recently used) entries. The device→host sync happens
        OUTSIDE the lock — an export must not stall admissions."""
        with self._prefix_lock:
            if self._prefix_cache is None:
                return []
            if keys is None:
                sel = list(self._prefix_cache.items())
            else:
                sel = []
                for k in keys:
                    k = tuple(int(t) for t in k)
                    entry = self._prefix_cache.get(k)
                    if entry is not None:
                        sel.append((k, entry))
            if limit is not None and len(sel) > limit:
                sel = sel[-limit:]  # OrderedDict tail = most recently used
        out = []
        for key, stored in sel:
            # generic over the per-layer dict: int8 entries additionally
            # carry k_scale/v_scale arrays alongside the codes
            out.append((
                key,
                {
                    name: {
                        which: np.asarray(arr)  # kft: noqa[jax-sync] — peer-transfer export runs on an HTTP executor thread (lock already released), never the scheduler loop
                        for which, arr in lc.items()
                    }
                    for name, lc in stored.items()
                },
            ))
        self.stats["prefix_exported"] += len(out)
        return out

    def import_prefix_entries(self, entries) -> int:
        """Ingest peer-exported entries into this engine's prefix cache.
        Every entry is validated against THIS engine's layout (layer
        names, kv_heads, head_dim, 16-token quantum, max_seq fit) —
        an incompatible entry is skipped, never trusted. Returns the
        number of entries actually inserted; entries already present do
        not count (and are not touched — local recency wins)."""
        if self._prefix_cache is None:
            return 0
        prepared = []
        for key, tree in entries:
            key = tuple(int(t) for t in key)
            n16 = len(key)
            if n16 < 16 or n16 % 16 or n16 + 1 > self.max_seq:
                continue
            if (
                self._prefix_cache_tokens is not None
                and n16 > self._prefix_cache_tokens
            ):
                continue
            if self._span_reject(tree, n16) is not None:
                continue
            prepared.append((
                key,
                {
                    name: {
                        which: jnp.asarray(arr)
                        for which, arr in lc.items()
                    }
                    for name, lc in tree.items()
                },
            ))
        imported = 0
        with self._prefix_lock:
            for key, tree in prepared:
                if key in self._prefix_cache:
                    continue  # resident already: local recency wins
                self._insert_prefix_locked(key, tree)
                imported += 1
        self.stats["prefix_imported"] += imported
        return imported

    def _span_reject(self, tree, n16: int) -> str | None:
        """Why a wire KV tree (a prefix-cache entry, a shipped
        per-request span, or a host-tier blob — ONE validator guards
        every plane of the codec) cannot implant into THIS engine; None
        when it can. The key-SET check is the wire-level
        mixed-quantization discriminator: int8 trees carry
        ``k_scale``/``v_scale`` planes alongside the codes, float trees
        must not — a float engine would attend to raw codes, an int8
        engine has no scales to dequantize with."""
        H, D = self.cfg.kv_heads, self.cfg.head_dim
        if set(tree) != set(self.cache):
            return "layer names differ from this engine's model"
        quant = self.kv_quant == "int8"
        want_keys = (
            {"k", "v", "k_scale", "v_scale"} if quant else {"k", "v"}
        )
        want = (1, H, n16, D)
        want_scale = (1, H, n16)
        for name, lc in tree.items():
            if set(lc) != want_keys:
                return (
                    f"quantization mismatch: layer {name!r} carries "
                    f"{sorted(lc)} but this engine's kv_quant is "
                    f"{self.kv_quant!r}"
                )
            if np.shape(lc["k"]) != want or np.shape(lc["v"]) != want:
                return (
                    f"KV shape {np.shape(lc['k'])} != {want} "
                    "(kv_heads / head_dim / window mismatch)"
                )
            if quant and (
                np.shape(lc["k_scale"]) != want_scale
                or np.shape(lc["v_scale"]) != want_scale
            ):
                return f"scale plane shape != {want_scale}"
        return None

    def prepare_kv_span(self, ids, tree, meta) -> PreparedKVSpan:
        """Validate a shipped per-request KV span against THIS engine and
        device-put it for ``submit(kv_span=...)`` injection. Raises
        ValueError on ANY layout or quantization mismatch — callers
        (engine.fetch_kv_span) treat that as a failed ship and fall back
        to a local prefill, so a misconfigured pool pairing degrades to
        colocated behavior instead of corrupting a row."""
        try:
            real_len = int(meta["real_len"])
            first_tok = int(meta["first_tok"])
            valid = bool(meta["valid"])
        except (KeyError, TypeError, ValueError) as e:
            raise ValueError(f"kv span meta malformed: {e}") from None
        if real_len != len(ids):
            raise ValueError(
                f"kv span covers a {real_len}-token prompt; this request "
                f"has {len(ids)} tokens"
            )
        n16 = -(-real_len // 16) * 16
        if n16 + 1 > self.max_seq:
            raise ValueError(
                f"kv span window {n16} + 1 exceeds engine max_seq "
                f"{self.max_seq}"
            )
        reason = self._span_reject(tree, n16)
        if reason is not None:
            raise ValueError(f"kv span rejected: {reason}")
        jtree = {
            name: {which: jnp.asarray(arr) for which, arr in lc.items()}
            for name, lc in tree.items()
        }
        return PreparedKVSpan(
            jtree,
            {"real_len": real_len, "first_tok": first_tok, "valid": valid},
            n16,
        )

    def drop_prefix_cache(self) -> int:
        """Wipe every stored prefix entry (the chaos ``DropPrefixCache``
        seam, and warmup's pollution reset). Returns entries dropped."""
        with self._prefix_lock:
            if self._prefix_cache is None:
                return 0
            n = len(self._prefix_cache)
            self._prefix_cache.clear()
            self._prefix_lens.clear()
            self._prefix_lens_sorted = None
            self._prefix_tokens_stored = 0
            return n


class _AdmittedStream:
    """Iterator wrapper that releases exactly one admission slot however
    the stream ends: exhaustion, error, or close before first next()."""

    def __init__(self, gen, release):
        self._gen = gen
        self._release = release
        self._released = False

    def _release_once(self) -> None:
        if not self._released:
            self._released = True
            self._release()

    def __iter__(self):
        return self

    def __next__(self):
        try:
            return next(self._gen)
        except BaseException:  # StopIteration included: stream is over
            self._release_once()
            raise

    def close(self) -> None:
        try:
            self._gen.close()  # cancels the engine row (stream's finally)
        finally:
            self._release_once()


def _header_get(headers, name: str):
    """Read one x-kft-* header from a dict/CIMultiDict (deadline.py
    idiom: probe the exact lowercase name and its .title() spelling
    instead of lowercasing a copy per request)."""
    if not headers:
        return None
    val = headers.get(name)
    if val is None:
        val = headers.get(name.title())
    return val


def fetch_kv_span(
    engine: LMEngine,
    peer: str,
    model_name: str,
    ids,
    temperature: float,
    *,
    trace: Any = None,
    timeout_s: float = 30.0,
    seed: int | None = None,
) -> PreparedKVSpan | None:
    """Decode-replica side of a disaggregated dispatch: pull the finished
    KV span for ``ids`` from the prefill-pool replica at ``peer`` (the
    gateway-stamped ``x-kft-prefill-peer`` URL) and validate it against
    ``engine``. Returns a :class:`PreparedKVSpan` ready for
    ``submit(kv_span=...)`` — or None on ANY failure (peer down or
    killed mid-ship, bad payload, layout/quantization mismatch, chaos
    ``DropKVShip``), in which case the caller runs a normal local
    prefill: disaggregation is an optimization, never a correctness
    dependency, and a broken ship leg must stay invisible to the client.

    Runs on an HTTP-executor / SSE-pump thread (blocking urllib), never
    the scheduler loop. The ``kv.ship`` span bridges the prefill and
    decode legs of ONE trace id: its context is forwarded to the peer,
    so the prefill replica's engine span lands under the same trace the
    gateway minted."""
    import json as _json
    import urllib.request

    t0 = time.monotonic()
    span = TRACER.span("kv.ship", parent=trace)
    if span:
        span.set_attr("peer", peer)
        span.set_attr("model", model_name)
        span.set_attr("prompt_tokens", len(ids))
    try:
        hook = engine._fault_hooks.get("kv_ship")
        if hook is not None:
            hook(engine)  # chaos seam: DropKVShip raises here
        payload = {
            "ids": [int(t) for t in ids], "temperature": float(temperature)
        }
        if seed is not None:
            # resume determinism: the peer's first sampled token (riding
            # the span meta) must come from the same seeded stream
            payload["seed"] = int(seed)
        body = _json.dumps(payload).encode()
        hdrs = {"Content-Type": "application/json"}
        if span:
            hdrs[TRACE_HEADER] = span.header()
        req = urllib.request.Request(
            f"{peer.rstrip('/')}/v2/models/{model_name}/kv_span:prefill",
            data=body, headers=hdrs, method="POST",
        )
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            blob = resp.read()
        entries, meta = decode_kv_entries(blob)
        if not entries or meta is None:
            raise ValueError("span payload missing entries or meta")
        prepared = engine.prepare_kv_span(ids, entries[0][1], meta)
        n = len(blob)
        KV_SHIP_BYTES.labels(model=model_name, direction="import").inc(n)
        KV_SHIP_MS.observe((time.monotonic() - t0) * 1e3)
        engine.stats["kv_ship_bytes"] += n
        if span:
            span.set_attr("bytes", n)
            span.end()
        return prepared
    except Exception as e:  # noqa: BLE001 — EVERY ship failure (network,
        # payload, validation, chaos) degrades to a local prefill on the
        # decode replica; the client never sees it
        engine.stats["kv_ship_fallbacks"] += 1
        if span:
            span.set_attr("error", f"{type(e).__name__}: {e}")
            span.end("error")
        return None


class LMEngineModel(LMRuntimeModel):
    """Engine-backed serving model: the ``causal-lm`` runtime's data path
    (tokenizer, preprocess, postprocess) with continuous batching
    underneath. Rows from concurrent HTTP requests share one decode batch;
    the async call path hands each row to the engine on an executor thread
    so the event loop never blocks on generation."""

    def __init__(
        self, name, storage_path=None, *, max_batch=8, max_seq=None,
        chunk_steps=8, prefix_cache_entries=0, prefix_cache_tokens=None,
        prefill_chunk=None, mesh=None, rules=None,
        kv_pool_tokens=None, page_size=64, pipeline_depth=1,
        spec_draft_tokens=0, spec_ngram=3,
        kv_quant="none", host_kv_bytes=0,
        watchdog=True,
        watchdog_interval_s=0.5, watchdog_wedge_factor=8.0,
        watchdog_min_wedge_s=30.0, **kwargs,
    ):
        super().__init__(name, storage_path, **kwargs)
        self._engine_max_batch = max_batch
        self._engine_chunk = chunk_steps
        self._engine_host_kv_bytes = host_kv_bytes
        self._engine_prefix_entries = prefix_cache_entries
        self._engine_prefix_tokens = prefix_cache_tokens
        self._engine_mesh = mesh
        self._engine_rules = rules
        self._engine_prefill_chunk = prefill_chunk
        self._engine_pool_tokens = kv_pool_tokens
        self._engine_page_size = page_size
        self._engine_pipeline_depth = pipeline_depth
        self._engine_spec_draft = spec_draft_tokens
        self._engine_spec_ngram = spec_ngram
        self._engine_kv_quant = kv_quant
        self._engine_max_seq = max_seq or (
            self.buckets.seq_lens[-1] + self.max_new_tokens
        )
        self.engine: LMEngine | None = None
        self._executor = None
        #: engine watchdog (serve/watchdog.py): supervises this model's
        #: engine slot, flips ``self.ready`` during restarts
        self.watchdog = None
        self._watchdog_on = watchdog
        self._watchdog_interval = watchdog_interval_s
        self._watchdog_factor = watchdog_wedge_factor
        self._watchdog_min_wedge = watchdog_min_wedge_s
        # admission control happens HERE, on the caller's thread: the
        # private executor is sized max_batch, so without this check excess
        # requests would queue invisibly in the executor (never reaching
        # the engine's own bounded queue) and wait unboundedly
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        #: called after every supervised engine restart — the DataPlane
        #: registers here to zero its per-model load signals so the
        #: gateway/autoscaler never size against pre-restart load
        self._restart_listeners: list = []

    def add_restart_listener(self, fn) -> None:
        self._restart_listeners.append(fn)

    def _make_engine(self) -> LMEngine:
        """One engine instance from the stored knobs — load() builds the
        first, the watchdog's supervised restart builds replacements
        (fresh KV cache / pager / prefix cache / carry; params reused —
        they are never donated, only the cache is)."""
        eng = LMEngine(
            self._model, self.config, self._params,
            max_batch=self._engine_max_batch,
            max_seq=self._engine_max_seq,
            chunk_steps=self._engine_chunk,
            prefill_buckets=self.buckets.seq_lens,
            eos_id=self.eos_id,
            prefix_cache_entries=self._engine_prefix_entries,
            prefix_cache_tokens=self._engine_prefix_tokens,
            prefill_chunk=self._engine_prefill_chunk,
            mesh=self._engine_mesh,
            rules=self._engine_rules,
            kv_pool_tokens=self._engine_pool_tokens,
            page_size=self._engine_page_size,
            pipeline_depth=self._engine_pipeline_depth,
            spec_draft_tokens=self._engine_spec_draft,
            spec_ngram=self._engine_spec_ngram,
            kv_quant=self._engine_kv_quant,
            host_kv_bytes=self._engine_host_kv_bytes,
        )
        # engine spans and TTFT/TPOT histograms label by serving model
        eng.model_name = self.name
        return eng

    def restart_engine(self, err: Exception | None = None) -> LMEngine:
        """Tear down and rebuild the engine's device state. The watchdog's
        rebuild hook; also callable directly by operators. The old engine
        must already be poisoned/stopped — its wedged thread (if any) is
        abandoned and exits on its own."""
        self.engine = self._make_engine().start()
        # the fresh engine starts with zeroed stats and a cold decode-gap
        # EWMA; the admission count must match, or load signals report
        # rows the poison pass already failed. Requests still unwinding
        # release later — _release clamps at zero so they cannot go
        # negative against this reset.
        with self._inflight_lock:
            self._inflight = 0
        for fn in list(self._restart_listeners):
            try:
                fn()
            except Exception:  # noqa: BLE001 — a listener must not block
                pass  # the restart; readiness recovery comes first
        return self.engine

    def _set_ready(self, ready: bool) -> None:
        # the watchdog flips this first on a trip: /v2/health/ready goes
        # 503 and the gateway's outlier ejection routes around the replica
        self.ready = ready

    def load(self) -> bool:
        super().load()  # restores params, device_put
        # a PRIVATE executor for blocking engine.submit calls: the loop's
        # default executor can be tiny (min(32, cpus+4) — 5 on a 1-cpu
        # host) and shared; if other blocking work fills it, submits queue
        # behind it and the server deadlocks while the engine sits idle
        import concurrent.futures

        self._executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=self._engine_max_batch,
            thread_name_prefix=f"lm-engine-{self.name}",
        )
        self.engine = self._make_engine().start()
        if self._watchdog_on:
            from kubeflow_tpu.serve.watchdog import (
                EngineWatchdog,
                WatchdogConfig,
            )

            self.watchdog = EngineWatchdog(
                lambda: self.engine,
                self.restart_engine,
                on_ready=self._set_ready,
                config=WatchdogConfig(
                    interval_s=self._watchdog_interval,
                    wedge_factor=self._watchdog_factor,
                    min_wedge_s=self._watchdog_min_wedge,
                ),
                model_name=self.name,
            ).start()
        return True

    def unload(self) -> None:
        if self.watchdog is not None:
            self.watchdog.stop()
            self.watchdog = None
        if self.engine is not None:
            self.engine.stop()
            self.engine = None
        if self._executor is not None:
            self._executor.shutdown(wait=False, cancel_futures=True)
            self._executor = None
        super().unload()

    def warmup(self) -> None:
        """Compile every prefill bucket + the chunk program — which, with
        ``spec_draft_tokens=K`` on, IS the (K+1)-position speculative
        verify program (each warmup submit decodes at least one chunk, so
        the first speculative request never pays a compile mid-traffic) —
        and (when prefix caching is on) the implant/extract/suffix-prefill
        programs. Distinct token patterns per bucket stop one warmup
        prompt prefix-hitting another (which would skip the larger
        bucket's compile), and the warmup entries are cleared so they
        never occupy real LRU capacity. Warmup traffic must not pollute
        production metrics: every counter — including the spec acceptance
        gauges, which warmup's repeated-token prompts would skew —
        restarts at zero."""
        eng = self.engine
        vocab = self.config.vocab_size
        for i, s in enumerate(self.buckets.seq_lens):
            eng.submit([2 + i % (vocab - 2)] * s, max_new_tokens=2)
        if eng.spec_k:
            # a repeated-pattern prompt guarantees the drafter's match
            # path (nonzero draft_len) traces through verify at least
            # once — budget > K so a full accepted span fits (clamped to
            # the engine's per-row layout bound)
            s0 = self.buckets.seq_lens[0]
            cap = eng.max_seq - s0
            if cap >= 2:
                eng.submit(
                    ([3, 5, 7] * s0)[:s0],
                    max_new_tokens=min(eng.spec_k + 2, cap),
                )
        if eng._prefix_cache is not None:
            eng.drop_prefix_cache()
            n_b = len(self.buckets.seq_lens)
            for j, n16 in enumerate(
                range(16, self.buckets.seq_lens[-1], 16)
            ):
                if (
                    n16 + 16 + 2 > eng.max_seq
                    or eng._bucket(n16 + 1) + 2 > eng.max_seq
                ):
                    break
                tok = 2 + (n_b + j) % (vocab - 2)
                # store an n16-long prefix: compiles extract(n16)
                eng.submit([tok] * (n16 + 1), max_new_tokens=2)
                # the suffix-prefill program is keyed by SUFFIX shape alone
                # (implant by n16), so sweep the sbucket shapes once (j==0)
                # and afterwards one hit per n16 compiles its implant
                sweep = (
                    range(16, self.buckets.seq_lens[-1] + 1, 16)
                    if j == 0 and eng.prefill_chunk is None
                    else (16,)
                )  # with prefill_chunk, every piece is one shape — no sweep
                for si, sbucket in enumerate(sweep):
                    slen = sbucket - 15
                    try:
                        full_bucket = eng._bucket(n16 + slen)
                    except ValueError:
                        break
                    if (
                        n16 + sbucket + 2 > eng.max_seq
                        or full_bucket + 2 > eng.max_seq
                    ):
                        break
                    # distinct per step: a repeated tail would let the
                    # previous step's store-on-hit extension absorb this
                    # step's suffix into an already-compiled shape
                    tail_tok = 2 + (n_b + j + 1 + si) % (vocab - 2)
                    if tail_tok == tok:
                        tail_tok = 2 + (tail_tok - 1) % (vocab - 2)
                    eng.submit(
                        [tok] * n16 + [tail_tok] * slen, max_new_tokens=2
                    )
            eng.drop_prefix_cache()
        # warmup traffic must not pollute production metrics (/metrics
        # gauges, hit rates, spec acceptance) — counters restart at zero
        for key in eng.stats:
            eng.stats[key] = 0
        for key in eng.overlap:
            eng.overlap[key] = 0 if key == "carry_uploads" else 0.0

    def _pull_kv_span(self, row, peer, trace, deadline, *, ids=None,
                      seed=None):
        """Fetch + validate this row's KV span from its prefill peer
        (None ⇒ no disaggregation, or any ship failure → local prefill).
        Runs on the executor / SSE-pump thread — never the event loop.
        ``ids`` overrides the row's prompt (a resume dispatch pulls the
        span for prompt+committed, so the peer prefills the FULL resumed
        context and this replica runs zero prefill pieces)."""
        if not peer:
            return None
        eng = self.engine
        if eng is None:
            return None
        timeout_s = 30.0
        if deadline is not None:
            timeout_s = max(0.1, min(timeout_s, deadline - time.monotonic()))
        return fetch_kv_span(
            eng, peer, self.name, ids if ids is not None else row["ids"],
            row["temperature"], trace=trace, timeout_s=timeout_s, seed=seed,
        )

    def _row_budget(self, row) -> int:
        """Per-request output budget (vLLM ``max_tokens`` analog): the
        row's requested ``max_new_tokens`` clamped to the model cap —
        the cap bounds compiled shapes, so a request may only shrink it."""
        req = row.get("max_new_tokens")
        if req is None:
            return self.max_new_tokens
        return max(1, min(int(req), self.max_new_tokens))

    def _submit_row(
        self, row, deadline: float | None = None, priority: int = 0,
        trace: Any = None, peer: str | None = None,
        session: str | None = None, seed: int | None = None,
    ) -> dict:
        kv_span = self._pull_kv_span(row, peer, trace, deadline, seed=seed)
        toks = self.engine.submit(
            row["ids"],
            max_new_tokens=self._row_budget(row),
            temperature=row["temperature"],
            deadline=deadline,
            priority=priority,
            trace=trace,
            kv_span=kv_span,
            session=session,
            seed=seed,
        )
        return {"token_ids": toks}

    def _admit(self, n_rows: int) -> None:
        eng = self.engine  # snapshot: unload() may null it concurrently
        if eng is None:
            raise RuntimeError(f"model {self.name!r} is unloaded")
        cap = self._engine_max_batch + eng.max_queue
        with self._inflight_lock:
            if self._inflight + n_rows > cap:
                raise EngineOverloaded(
                    f"{self._inflight} rows in flight (capacity {cap})"
                )
            self._inflight += n_rows

    def _release(self, n_rows: int) -> None:
        with self._inflight_lock:
            # clamped: a watchdog restart zeroes the count while poisoned
            # requests are still unwinding toward their finally-release
            self._inflight = max(0, self._inflight - n_rows)

    def predict(self, rows, headers=None) -> list[dict]:
        # sync path (gRPC, batcher): fan rows out so they share the decode
        # batch with each other and with everyone else's requests. Release
        # only after EVERY row settles — an early release while sibling
        # rows still run would let new requests past the admission cap.
        import concurrent.futures as cf

        deadline = deadline_from_headers(headers)
        priority = priority_from_headers(headers)
        ctx = ctx_from_headers(headers)
        peer = _header_get(headers, PREFILL_PEER_HEADER)
        session = _header_get(headers, SESSION_HEADER)
        seed = seed_from_headers(headers)
        self._admit(len(rows))
        futs = [
            self._executor.submit(
                self._submit_row, r, deadline, priority, ctx, peer,
                session, seed,
            )
            for r in rows
        ]
        try:
            cf.wait(futs)
        finally:
            self._release(len(rows))
        return [f.result() for f in futs]

    def stream_row_tokens(self, row, headers=None):
        """Token-chunk iterator for one preprocessed row — the server's
        generate_stream (SSE) hook. Admission happens EAGERLY (here, not at
        first next()) so overload raises before the server commits a 200;
        the wrapper guarantees release even for a stream that is closed
        before its first next() (a bare generator's finally wouldn't run)."""
        deadline = deadline_from_headers(headers)
        priority = priority_from_headers(headers)
        ctx = ctx_from_headers(headers)
        peer = _header_get(headers, PREFILL_PEER_HEADER)
        session = _header_get(headers, SESSION_HEADER)
        seed = seed_from_headers(headers)
        resume = resume_from_headers(headers)
        self._admit(1)

        def run():
            # the peer pull (blocking HTTP) runs HERE — at first next(),
            # on the SSE pump thread — never on the event loop. A resume
            # dispatch pulls the span for prompt+committed: the peer
            # prefills the FULL resumed context, so this replica admits
            # with zero prefill pieces
            span_ids = row["ids"] if not resume else (
                list(row["ids"]) + list(resume)
            )
            kv_span = self._pull_kv_span(
                row, peer, ctx, deadline, ids=span_ids, seed=seed
            )
            yield from self.engine.stream(
                row["ids"],
                max_new_tokens=self._row_budget(row),
                temperature=row["temperature"],
                deadline=deadline,
                priority=priority,
                trace=ctx,
                kv_span=kv_span,
                session=session,
                resume_tokens=resume,
                seed=seed,
            )

        return _AdmittedStream(run(), lambda: self._release(1))

    async def __call__(self, payload, headers=None):
        import asyncio

        rows = self.preprocess(payload, headers)
        deadline = deadline_from_headers(headers)
        priority = priority_from_headers(headers)
        ctx = ctx_from_headers(headers)
        peer = _header_get(headers, PREFILL_PEER_HEADER)
        session = _header_get(headers, SESSION_HEADER)
        seed = seed_from_headers(headers)
        self._admit(len(rows))
        try:
            loop = asyncio.get_running_loop()
            # return_exceptions: wait for EVERY row before releasing the
            # inflight count, else a fast-failing row under-counts while
            # its siblings still occupy engine capacity
            outs = await asyncio.gather(
                *[
                    loop.run_in_executor(
                        self._executor, self._submit_row, r, deadline,
                        priority, ctx, peer, session, seed,
                    )
                    for r in rows
                ],
                return_exceptions=True,
            )
        finally:
            self._release(len(rows))
        for o in outs:
            if isinstance(o, BaseException):
                raise o
        return self.postprocess(list(outs), headers)


def engine_from_runtime(
    runtime, *, max_batch: int = 8, max_seq: int = 256, **kw
) -> LMEngine:
    """Wrap a loaded LMRuntimeModel's model+params in an engine."""
    if not runtime.ready:
        runtime.load()
    return LMEngine(
        runtime._model, runtime.config, runtime._params,
        max_batch=max_batch, max_seq=max_seq,
        eos_id=runtime.eos_id, **kw,
    ).start()
