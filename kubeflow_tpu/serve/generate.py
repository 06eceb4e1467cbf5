"""Generative LM serving: KV-cache decode, whole-generation-on-device.

Reference analog: the KServe HuggingFace runtime's generative path and its
optional vLLM backend ([kserve] python/huggingfaceserver — UNVERIFIED,
mount empty, SURVEY.md §0): prompt in → tokens stream out, with a KV cache
so each new token costs one decode step, not a re-prefill.

TPU-first design decisions:

- **The entire generation is ONE jitted program**: prefill + a
  ``lax.scan`` over decode steps runs on-device and returns the whole
  completion. A per-token host round-trip would pay the host↔device
  latency per token; scanning makes generation latency ≈ compute.
- **Bucketed shapes**: prompts pad to (batch, prefill) buckets and the
  scan length is the fixed configured ``max_new_tokens``, so XLA compiles
  a small closed set of programs (same discipline as serve/model.py).
- **Ragged batches via kv masks**: right-padded prompts write pad
  keys/values into the cache; a per-row validity mask excludes them from
  every attention, and per-row positions keep RoPE continuous across the
  prompt→generation boundary.
- EOS rows keep stepping (SPMD-friendly: no data-dependent early exit)
  but emit ``pad_id``; the host trims.
"""

from __future__ import annotations

import time
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    init_kv_cache,
)
from kubeflow_tpu.serve.model import BucketSpec, Model


def decode_kv_mask(kpos, prompt_len, gen_start, slot, window=None):
    """(B, T) cache-slot mask for ONE decode step over a
    ``[prompt | gap | gen]`` row layout: prompt slots ``[0, prompt_len)``
    sit at their token positions; gen slot ``s`` in ``[gen_start, slot]``
    holds token position ``prompt_len + (s - gen_start)`` (the gap between
    ``prompt_len`` and ``gen_start`` is padding and never attended).

    ``window`` applies sliding-window attention in TOKEN-POSITION space:
    the query (at position ``prompt_len + slot - gen_start``) keeps keys
    with position > query pos - window, which in the gen region reduces to
    ``s > slot - window`` (row-independent). Shared by make_generate_fn and
    LMEngine so the window math cannot diverge between them; scalars and
    (B,) arrays both broadcast."""
    pl = jnp.atleast_1d(jnp.asarray(prompt_len))[:, None]
    gs = jnp.atleast_1d(jnp.asarray(gen_start))[:, None]
    sl = jnp.atleast_1d(jnp.asarray(slot))[:, None]
    k = kpos[None, :]
    prompt_keep = k < pl
    gen_keep = (k >= gs) & (k <= sl)
    if window is not None:
        qpos = pl + sl - gs
        prompt_keep &= k > qpos - window
        gen_keep &= k > sl - window
    return prompt_keep | gen_keep


def sample_logits(logits, rng, temperature):
    """Per-row greedy/temperature sampling over (B, V) logits.

    Temperature is PER ROW (B,): co-batched greedy and sampling requests
    must each get what they asked for. Shared by ``make_generate_fn`` and
    the engine's chunk/prefill programs (the carry-friendly step seam), so
    the two decode paths cannot diverge in sampling semantics — the
    engine's token-parity contract against this module depends on it."""
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    drawn = jax.random.categorical(rng, scaled, axis=-1)
    return jnp.where(temperature <= 0.0, greedy, drawn)


def make_generate_fn(
    model: TransformerLM,
    cfg: TransformerConfig,
    *,
    max_new_tokens: int,
    eos_id: int,
    pad_id: int = 0,
):
    """Builds ``(params, prompt, prompt_len, rng, temperature) → tokens``:
    prefill + scan-decode, jittable per (batch, prefill_len) bucket."""

    sample = sample_logits
    if len({kind.window for kind in cfg.kinds}) > 1:
        # decode_kv_mask below is one slot mask for every layer
        raise ValueError(
            "this dense-cache path masks every layer alike; a model whose "
            "layer kinds differ in their window is served by LMEngine"
        )
    window = cfg.kind(0).window

    def generate(params, prompt, prompt_len, rng, temperature):
        B, P = prompt.shape
        max_len = P + max_new_tokens
        if not cfg.use_rope and max_len > cfg.max_seq_len:
            # learned positions gather with clipping — exceeding the table
            # would silently reuse the last row's embedding
            raise ValueError(
                f"prompt bucket {P} + max_new_tokens {max_new_tokens} "
                f"exceeds max_seq_len {cfg.max_seq_len}"
            )
        cache = init_kv_cache(cfg, B, max_len)
        logits, cache = model.apply(
            {"params": params}, prompt, cache=cache, cache_index=0
        )
        # each row's next-token logits sit at its LAST REAL prompt slot
        last = jnp.take_along_axis(
            logits, (prompt_len - 1)[:, None, None], axis=1
        )[:, 0]
        rng, sub = jax.random.split(rng)
        first = sample(last, sub, temperature)
        valid0 = first != eos_id
        done0 = ~valid0
        first = jnp.where(done0, pad_id, first)
        kpos = jnp.arange(max_len)

        def step(carry, j):
            cache, tok, done, rng = carry
            rng, sub = jax.random.split(rng)
            slot = P + j  # cache slot for THIS token (same for all rows)
            # attend: real prompt slots + generated slots up to and incl.
            # this one; never pad slots, never unwritten slots
            positions = (prompt_len + j)[:, None]  # rope continues per row
            kv_mask = decode_kv_mask(
                kpos, prompt_len, P, slot, window
            )
            lg, cache = model.apply(
                {"params": params},
                tok[:, None],
                cache=cache,
                cache_index=slot,
                positions=positions,
                kv_mask=kv_mask,
            )
            nxt = sample(lg[:, 0], sub, temperature)
            # a slot holds real content iff no prior EOS and this draw
            # isn't EOS — pad_id may be a legitimate vocab token, so the
            # validity channel (not a pad sentinel) is the truth
            valid = ~done & (nxt != eos_id)
            done = done | (nxt == eos_id)
            nxt = jnp.where(done, pad_id, nxt)
            return (cache, nxt, done, rng), (nxt, valid)

        (_, _, _, _), (rest, rest_valid) = jax.lax.scan(
            step,
            (cache, first, done0, rng),
            jnp.arange(max_new_tokens - 1),
        )
        tokens = jnp.concatenate([first[:, None], rest.T], axis=1)
        valid = jnp.concatenate([valid0[:, None], rest_valid.T], axis=1)
        # (B, max_new) tokens + per-row count of real tokens
        return tokens, valid.sum(axis=1)

    return generate


def _restore_lm_params(storage_path: str):
    """Accepts BOTH checkpoint layouts a user will actually have:

    1. a ``train.Checkpointer`` directory (Orbax CheckpointManager: step
       subdirs holding the full TrainState) — the train→serve handoff:
       restore the latest step, take its ``params``;
    2. a bare ``StandardCheckpointer`` params directory.
    """
    import os

    import orbax.checkpoint as ocp

    path = os.path.abspath(storage_path)
    if not os.path.isdir(path):
        # fail closed with the true cause — and never let the manager probe
        # mkdir a mistyped/unmounted path into existence
        raise RuntimeError(
            f"LM storage_path {path!r} does not exist (failed mount / typo?)"
        )
    step = None
    mgr = None
    try:
        mgr = ocp.CheckpointManager(
            path, options=ocp.CheckpointManagerOptions(create=False)
        )
        step = mgr.latest_step()
    except Exception:  # noqa: BLE001 — not a manager layout; bare fallback
        step = None
    if step is not None:
        # a genuine train checkpoint: restore errors are REAL and must
        # surface (corrupt step, version mismatch), not be masked by a
        # nonsensical bare-layout fallback error
        try:
            try:
                tree = mgr.restore(step)
            except (KeyError, ValueError):
                # older orbax can't infer the handler from saved metadata
                # and needs the restore args spelled out
                tree = mgr.restore(step, args=ocp.args.StandardRestore())
        except Exception as e:
            raise RuntimeError(
                f"LM storage_path {path!r} is a train checkpoint "
                f"(latest step {step}) but restoring it failed: {e}"
            ) from e
        finally:
            mgr.close()
        if isinstance(tree, Mapping) and "params" in tree:
            return tree["params"]
        return tree
    if mgr is not None:
        mgr.close()
    with ocp.StandardCheckpointer() as ckptr:
        return ckptr.restore(path)


class LMRuntimeModel(Model):
    """Causal-LM serving runtime: text/ids in → generated ids (+text) out.

    v1 request rows: ``"prompt text"`` or ``{"text": ..}`` or
    ``{"input_ids": [...]}``; optional per-request ``temperature`` (0 =
    greedy). Response rows: ``{"token_ids": [...], "text": ...?}``.
    """

    def __init__(
        self,
        name: str,
        storage_path: str | None = None,
        *,
        config: TransformerConfig | None = None,
        buckets: BucketSpec | None = None,
        max_new_tokens: int = 32,
        eos_id: int = 1,
        seed: int = 0,
        **_ignored: Any,
    ):
        super().__init__(name)
        self.config = config or TransformerConfig(causal=True)
        if not self.config.causal:
            raise ValueError("LMRuntimeModel needs a causal TransformerConfig")
        self.buckets = buckets or BucketSpec(
            batch_sizes=(1, 4), seq_lens=(32, 128)
        )
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self._storage_path = storage_path
        self._model = TransformerLM(self.config)
        self._params = None
        self._generate = None
        self._rng = jax.random.PRNGKey(seed)
        from collections import deque

        from kubeflow_tpu.serve.runtimes import SimpleTokenizer

        self.tokenizer = SimpleTokenizer(self.config.vocab_size)
        # bounded: long-lived servers must not grow a list per request
        self.stats = {"requests": 0, "generate_ms": deque(maxlen=1024)}
        if not self.config.use_rope:
            worst = self.buckets.seq_lens[-1] + max_new_tokens
            if worst > self.config.max_seq_len:
                raise ValueError(
                    f"largest seq bucket {self.buckets.seq_lens[-1]} + "
                    f"max_new_tokens {max_new_tokens} exceeds "
                    f"max_seq_len {self.config.max_seq_len}"
                )

    # -- lifecycle ------------------------------------------------------- #

    def load(self) -> bool:
        if self._storage_path is not None:
            params = _restore_lm_params(self._storage_path)
        else:  # fresh weights: latency benchmarking / tests
            params = self._model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
            )["params"]
        self._params = jax.device_put(params)
        jax.block_until_ready(self._params)
        self._generate = jax.jit(
            make_generate_fn(
                self._model,
                self.config,
                max_new_tokens=self.max_new_tokens,
                eos_id=self.eos_id,
            )
        )
        self.ready = True
        return True

    def unload(self) -> None:
        self._params = None
        self._generate = None
        self.ready = False

    def warmup(self) -> None:
        for b in self.buckets.batch_sizes:
            for s in self.buckets.seq_lens:
                self._run(
                    np.zeros((b, s), np.int32),
                    np.full((b,), s, np.int32),
                    np.zeros((b,), np.float32),
                )

    # -- data path ------------------------------------------------------- #

    def preprocess(self, payload: Any, headers: Mapping[str, str] | None = None):
        if isinstance(payload, Mapping) and "instances" in payload:
            payload = payload["instances"]
        rows = []
        for inst in payload:
            temperature = 0.0
            budget = None
            if isinstance(inst, str):
                ids = self.tokenizer.encode(inst)
            elif isinstance(inst, Mapping):
                temperature = float(inst.get("temperature", 0.0))
                if inst.get("max_new_tokens") is not None:
                    # per-request output budget (vLLM max_tokens analog);
                    # engine-backed runtimes clamp it to the model cap
                    budget = int(inst["max_new_tokens"])
                    if budget < 1:
                        raise ValueError(
                            f"max_new_tokens must be >= 1, got {budget}"
                        )
                if isinstance(inst.get("text"), str):
                    ids = self.tokenizer.encode(inst["text"])
                else:
                    ids = list(inst["input_ids"])
            else:
                ids = list(inst)
            ids = [int(t) % self.config.vocab_size for t in ids]
            if not ids:
                raise ValueError("empty prompt")
            rows.append({
                "ids": ids, "temperature": temperature,
                "max_new_tokens": budget,
            })
        if not rows:
            raise ValueError("empty request")
        return rows

    def _run(self, prompt, prompt_len, temperature):
        self._rng, sub = jax.random.split(self._rng)
        tokens, n_valid = self._generate(
            self._params, prompt, prompt_len, sub,
            jnp.asarray(temperature, jnp.float32),
        )
        return np.asarray(tokens), np.asarray(n_valid)

    def predict(self, rows, headers=None) -> list[dict]:
        n = len(rows)
        longest = max(len(r["ids"]) for r in rows)
        bb = self.buckets.bucket_batch(n)
        bs = self.buckets.bucket_seq(longest)
        prompt = np.zeros((bb, bs), np.int32)
        plen = np.ones((bb,), np.int32)  # pad rows: len 1, harmless
        temperature = np.zeros((bb,), np.float32)  # per-row, honored per-row
        for i, r in enumerate(rows):
            prompt[i, : len(r["ids"])] = r["ids"]
            plen[i] = len(r["ids"])
            temperature[i] = r["temperature"]
        t0 = time.perf_counter()
        out, n_valid = self._run(prompt, plen, temperature)
        self.stats["generate_ms"].append((time.perf_counter() - t0) * 1e3)
        self.stats["requests"] += 1
        # trim by the VALIDITY COUNT from the device — pad_id can be a
        # legitimate vocab token, so searching for it would truncate output
        return [
            {"token_ids": [int(t) for t in out[i, : n_valid[i]]]}
            for i in range(n)
        ]

    def postprocess(self, outputs, headers=None) -> Any:
        return {"predictions": outputs}
