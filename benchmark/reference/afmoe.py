"""Arcee Trinity's language model (``model_type: afmoe``; Hugging Face
``transformers`` ``modeling_afmoe.py``, ``AfmoeForCausalLM``; Arcee's
Trinity report) as plain ``jax.numpy``: float32, highest matmul precision,
no kernel, cache or batching, and nothing imported from ``kubeflow_tpu``.

The forward pass, for tokens ``t_0 .. t_{S-1}``::

    x = E[t] * sqrt(hidden)                                  (mup_enabled)
    per layer l of kind layer_types[l]:
      a = n_in(x);  q, k, v, g = a W_q, a W_k, a W_v, a W_g
      q, k = n_q(q), n_k(k)            per head, over the head's width
      sliding: rotate q, k (half-split pairs, base rope_theta); key j is
               visible to query i iff i - sliding_window < j <= i
      full:    no positional term; j <= i
      o = softmax(q k^T / sqrt(head_dim) + mask) v        (grouped KV heads)
      x = x + n_post_attn((o * sigmoid(g)) W_o)
      b = n_pre_mlp(x);  x = x + n_post_mlp(FFN(b))
    logits = n_f(x) W_head

``FFN`` of the first ``num_dense_layers`` layers is ``(silu(b W_gate) * b
W_up) W_down`` at ``intermediate_size``. Of the others: ``s = sigmoid(b
W_r)`` (float32), ``sel = top_k(s + bias)``, ``w_e = route_scale * s_e /
(sum_{sel} s + 1e-20)``, the sum over ``sel`` of ``w_e`` times expert
``e``'s gated SiLU FFN at ``moe_intermediate_size``, plus the shared
expert's (every token, weight 1). Every ``n`` is an RMS norm with a learned
scale and ``rms_norm_eps``. No token is dropped whatever an expert's load.

What the published ``config.json`` does not pin, taken from the public
implementation and the report (each also under ``assumed`` in
``benchmark/configs/trinity-mini-l5.json`` — correct both in one place):

- the RMS norm of q and k per head, with one learned scale of ``head_dim``
  shared by the heads ("QK-norm"), applied before the rotation;
- the output gate ``sigmoid(a W_g)`` on the attention's result before
  ``W_o``, ``W_g`` of q's shape ("gated attention");
- four norms a block ("sandwich norm"): before and after the attention,
  before and after the FFN;
- rotary embeddings on sliding layers only; full layers carry no
  positional term;
- ``bias`` (``expert_bias``): added to the scores for the selection only,
  never to the weights; zero under seeded weights;
- the weights are normalised over the chosen experts (``route_norm``) and
  then scaled (``route_scale``), in that order.

Departures: a query sees the ``sliding_window`` keys ending at itself (the
repository's convention, as for Mistral; Hugging Face's mask admits one
more). The multi-token-prediction and vision parts of the checkpoint are
not part of the language model as served. ``experts_held`` (``first``,
``count``): the experts this chip holds of ``num_experts``; it routes over
all, computes the held ones' part and leaves the rest out (all of them in
``trinity-mini-l5``).

The experts' products are computed on the positions routed to them: the
routing is read back to the host, each expert's positions are laid in a
table padded to one width, and a loop over the experts gathers, multiplies
and scatter-adds — so an 8.7 k-token request fits beside the served
weights, one expert's float32 matrices alive at a time.
"""

from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rotate(x, positions, theta):
    """x: (S, H, D); rotate pairs (i, i + D/2) by position · theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, window, q_block):
    """q: (S, H, D), k/v: (S, Hkv, D): softmax over the causal window, a
    block of queries at a time."""
    S, H, D = q.shape
    groups = H // k.shape[1]
    k = jnp.repeat(k, groups, axis=1)
    v = jnp.repeat(v, groups, axis=1)
    kpos = jnp.arange(S)
    outs = []
    for s0 in range(0, S, q_block):
        qb = q[s0:s0 + q_block]
        qpos = s0 + jnp.arange(qb.shape[0])
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(F32(D))
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        scores = jnp.where(mask[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(outs, axis=0)


def _leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _gated(b, p):
    """(silu(b W_gate) * b W_up) W_down with ``p``'s three kernels."""
    w = lambda name: p[name]["kernel"].astype(F32)
    return (jax.nn.silu(b @ w("gate_proj")) * (b @ w("up_proj"))) @ w("down_proj")


def _attend(p, x, positions, kind, cfg, q_block):
    """The attention half of a layer: x + n_post_attn(...), and b."""
    H, Hkv, D = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, S = cfg["rms_norm_eps"], x.shape[0]
    w = lambda *path: _leaf(p, path).astype(F32)
    a = _rms_norm(x, w("ln1", "scale"), eps)
    q = (a @ w("attn", "q_proj", "kernel")).reshape(S, H, D)
    k = (a @ w("attn", "k_proj", "kernel")).reshape(S, Hkv, D)
    v = (a @ w("attn", "v_proj", "kernel")).reshape(S, Hkv, D)
    g = a @ w("attn", "gate_proj", "kernel")
    q = _rms_norm(q, w("attn", "q_norm", "scale"), eps)
    k = _rms_norm(k, w("attn", "k_norm", "scale"), eps)
    window = None
    if kind == "sliding_attention":
        q = _rotate(q, positions, cfg["rope_theta"])
        k = _rotate(k, positions, cfg["rope_theta"])
        window = cfg["sliding_window"]
    o = _attention(q, k, v, window, q_block).reshape(S, H * D)
    o = (o * jax.nn.sigmoid(g)) @ w("attn", "o_proj", "kernel")
    x = x + _rms_norm(o, w("ln1_post", "scale"), eps)
    return x, _rms_norm(x, w("ln2", "scale"), eps)


def _route(p, b, cfg):
    """(experts (S, k) int32, weights (S, k) f32) of every position."""
    s = jax.nn.sigmoid(b @ p["router"]["kernel"].astype(F32))
    _, sel = jax.lax.top_k(s + p["router"]["bias"].astype(F32), cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, sel, axis=1)
    if cfg["route_norm"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return sel, w * cfg["route_scale"]


def held_experts(cfg) -> tuple[int, int]:
    """(first, count) of the experts this chip holds of ``num_experts``."""
    held = cfg.get("experts_held") or {}
    return held.get("first", 0), held.get("count", cfg["num_experts"])


def _experts_routed(p, b, tables, cfg):
    """Each held expert's product on the positions routed to it. ``tables``:
    ``(positions (E_held, cap) int32, weights (E_held, cap) f32)``, unused
    slots at position 0 with weight 0."""
    pos, wts = tables

    def body(e, m):
        take = lambda name: jax.lax.dynamic_index_in_dim(
            p[name]["kernel"], e, axis=0, keepdims=False
        ).astype(F32)
        xs = jnp.take(b, pos[e], axis=0)
        y = (jax.nn.silu(xs @ take("gate_proj")) * (xs @ take("up_proj"))) @ take("down_proj")
        return m.at[pos[e]].add(y * wts[e][:, None])

    return jax.lax.fori_loop(0, pos.shape[0], body, jnp.zeros_like(b))


def _experts_whole(p, b, sel, w, cfg):
    """Every held expert on every position, masked by the routing: the
    same sum without the tables (small sizes only)."""
    first, count = held_experts(cfg)
    k = lambda name: p[name]["kernel"].astype(F32)
    h = jax.nn.silu(jnp.einsum("sd,edf->esf", b, k("gate_proj"))) * jnp.einsum(
        "sd,edf->esf", b, k("up_proj")
    )
    y = jnp.einsum("esf,efd->esd", h, k("down_proj"))
    # weight of expert e at position s: its w where chosen, else 0
    chosen = sel[None, :, :] == (first + jnp.arange(count))[:, None, None]
    return jnp.einsum("esd,es->sd", y, (chosen * w[None]).sum(-1))


def _tables(sel, w, cfg):
    """On the host: for each held expert the positions routed to it and
    their weights, padded to one power-of-two width."""
    first, count = held_experts(cfg)
    sel, w = np.asarray(sel), np.asarray(w)
    pos_of = np.repeat(np.arange(sel.shape[0]), sel.shape[1])
    local = sel.reshape(-1) - first
    mine = (local >= 0) & (local < count)
    local, pos_of, flat_w = local[mine], pos_of[mine], w.reshape(-1)[mine]
    loads = np.bincount(local, minlength=count)
    cap = 1 << max(int(loads.max(initial=1)) - 1, 0).bit_length()
    order = np.argsort(local, kind="stable")
    slot = np.arange(len(order)) - np.repeat(np.cumsum(loads) - loads, loads)
    pos = np.zeros((count, cap), np.int32)
    wts = np.zeros((count, cap), np.float32)
    pos[local[order], slot] = pos_of[order]
    wts[local[order], slot] = flat_w[order]
    return jnp.asarray(pos), jnp.asarray(wts)


def layer_kinds(cfg: Mapping) -> list[tuple[str, bool]]:
    """(attention kind, whether the FFN is dense) of each layer."""
    return [
        (cfg["layer_types"][i], i < cfg["num_dense_layers"])
        for i in range(cfg["num_hidden_layers"])
    ]


#: the keys of a configuration the layer programs read
_PROGRAM_KEYS = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
    "rope_theta", "sliding_window", "num_experts", "num_experts_per_tok",
    "route_norm", "route_scale",
)


@functools.lru_cache(maxsize=8)
def _programs(frozen: tuple, q_block: int) -> dict:
    """The jitted pieces of a layer, built once per configuration (a
    jitted lambda made anew per call would compile anew per call)."""
    cfg = dict(frozen)
    cfg["experts_held"] = dict(cfg["experts_held"])
    eps = cfg["rms_norm_eps"]
    return {
        "attend": jax.jit(
            lambda p, x, pos, kind: _attend(p, x, pos, kind, cfg, q_block),
            static_argnums=3,
        ),
        "dense": jax.jit(lambda p, b: _gated(b, p)),
        "route": jax.jit(lambda p, b: _route(p, b, cfg)),
        "experts": jax.jit(lambda p, b, tables: _experts_routed(p, b, tables, cfg)),
        "whole": jax.jit(lambda p, b, sel, w: _experts_whole(p, b, sel, w, cfg)),
        "join": jax.jit(lambda x, m, scale: x + _rms_norm(m, scale, eps)),
        "final": jax.jit(lambda x, scale: _rms_norm(x, scale, eps)),
        "head": jax.jit(lambda x, w, r: jnp.take(x, r, axis=0) @ w.astype(F32)),
    }


def _programs_of(cfg: Mapping, q_block: int) -> dict:
    first, count = held_experts(cfg)
    frozen = tuple((k, cfg[k]) for k in _PROGRAM_KEYS) + (
        ("experts_held", (("first", first), ("count", count))),
    )
    return _programs(frozen, q_block)


def _layer(f, p, x, positions, kind, is_dense, cfg, routed):
    """One layer on its own parameters ``p``. A function of its own so that
    nothing holds ``p`` once it returns: where ``params`` makes a layer's
    tree when asked for it (``benchmark/control.py``'s lower-precision
    copy, 3.4 GB in float32 for an expert layer), one is alive at a time."""
    x, b = f["attend"](p, x, positions, kind)
    if is_dense:
        m = f["dense"](p["mlp"], b)
    else:
        e = p["experts"]
        sel, w = f["route"](e, b)
        m = (
            f["experts"](e, b, _tables(sel, w, cfg)) if routed
            else f["whole"](e, b, sel, w)
        )
        m = m + f["dense"](e["shared"], b)
    return f["join"](x, m, p["ln2_post"]["scale"])


def hidden_states(params, tokens, cfg: Mapping, *, q_block: int = 512, routed: bool = True):
    """tokens (S,) → final-norm hidden states (S, hidden), float32. Each
    layer is a few jitted calls, so only one layer's float32 copies are
    alive at a time. ``routed=False``: every expert on every position."""
    f = _programs_of(cfg, q_block)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(F32)
        if cfg.get("mup_enabled"):
            x = x * F32(cfg["hidden_size"] ** 0.5)
        positions = jnp.arange(tokens.shape[0])
        for i, (kind, is_dense) in enumerate(layer_kinds(cfg)):
            x = _layer(f, params[f"layers_{i}"], x, positions, kind, is_dense, cfg, routed)
        return f["final"](x, params["ln_f"]["scale"])


def logits_at(params, tokens, rows, cfg: Mapping, **kw):
    """Logits (len(rows), vocab) at the given positions of one sequence."""
    x = hidden_states(params, tokens, cfg, **kw)
    with jax.default_matmul_precision("highest"):
        return _programs_of(cfg, kw.get("q_block", 512))["head"](
            x, params["unembed"]["kernel"], jnp.asarray(rows)
        )
