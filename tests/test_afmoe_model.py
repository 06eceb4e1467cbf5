"""Layers of more than one kind and dropless routed experts, against the
plain reference (`benchmark/reference/afmoe.py`) on seeded random weights
at a small size: hidden 64, 4 query / 2 KV heads of 32 (so that head_dim
!= hidden / heads), window 8, one dense layer then `sliding, sliding,
sliding, full` expert layers, 16 sigmoid-routed experts top-4 beside a
shared one, sequences of 40 (> window). Float32 on both sides."""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import afmoe
from benchmark.reference import afmoe as reference
from benchmark.weights import seeded_params
from kubeflow_tpu.models.transformer import (
    LayerKind,
    TransformerConfig,
    TransformerLM,
    init_paged_kv_cache,
    moe_every_kinds,
)
from kubeflow_tpu.parallel.expert import MoEConfig, dropless_moe_ffn, moe_ffn, route
from kubeflow_tpu.serve.engine import LMEngine

CFG = dict(
    family="afmoe", hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=32, layer_types=["sliding_attention"] * 4 + ["full_attention"],
    sliding_window=8, rope_theta=10000, rms_norm_eps=1e-5, num_dense_layers=1,
    intermediate_size=96, num_hidden_layers=5, num_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=48, num_shared_experts=1, score_func="sigmoid",
    route_norm=True, route_scale=2.826, mup_enabled=True, vocab_size=128,
    tie_word_embeddings=False, hidden_act="silu", max_position_embeddings=256,
    activation_dtype="float32", weight_dtype="float32",
)
SEQ = 40


def program(interpret=False, **over):
    pc = afmoe.program_config(CFG, attn_impl="reference", interpret_kernels=interpret)
    return dataclasses.replace(pc, **over) if over else pc


@pytest.fixture(scope="module")
def params():
    model = TransformerLM(program())
    return seeded_params(afmoe.abstract_params(model), 7, jnp.float32)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(2, CFG["vocab_size"], size=(8, SEQ)).astype(np.int32)


def reference_logits(params, seq, rows=None):
    rows = np.arange(len(seq)) if rows is None else rows
    return np.asarray(afmoe.reference_logits(params, np.asarray(seq, np.int32), rows, CFG))


# (a) the full forward pass ------------------------------------------------ #

@pytest.mark.parametrize("interpret", [False, True], ids=["ragged_dot", "gmm_kernel"])
def test_full_forward_matches_the_reference(params, tokens, interpret):
    """Both grouped products: XLA's on this CPU, and the Pallas kernel a
    TPU takes, under the interpreter."""
    got = TransformerLM(program(interpret)).apply({"params": params}, tokens[:2])
    for b in range(2):
        want = reference_logits(params, tokens[b])
        np.testing.assert_allclose(np.asarray(got[b]), want, atol=1e-4 * want.std())


def test_logits_at_wanted_positions_only(params, tokens):
    """The head computed at the positions asked for is the head computed
    everywhere, taken there."""
    model = TransformerLM(program())
    whole = model.apply({"params": params}, tokens[:2])
    at = jnp.asarray([[3, 39], [0, 17]])
    got = model.apply({"params": params}, tokens[:2], logit_positions=at)
    assert got.shape == (2, 2, CFG["vocab_size"])
    want = jnp.take_along_axis(whole, at[:, :, None], axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# (b) through the paged pool ------------------------------------------------ #

def paged_logits(pc, params, seqs, *, piece=16, page=16):
    """Rows of unequal length through the model's paged branch the way the
    engine drives it: each prompt (all but the row's last 6 tokens)
    prefilled alone in pieces of ``piece``, then the rows decoded together
    a token a step. Returns each row's logits at every position."""
    model = TransformerLM(pc)
    B = len(seqs)
    pages_per_row = -(-max(map(len, seqs)) // page)
    cache = init_paged_kv_cache(pc, (1 + B * pages_per_row) * page)
    table = 1 + np.arange(B * pages_per_row, dtype=np.int32).reshape(B, pages_per_row)
    out = [np.zeros((len(s), pc.vocab_size), np.float32) for s in seqs]
    apply = jax.jit(
        lambda cache, tok, pos, ok, table: model.apply(
            {"params": params}, tok, cache=cache, positions=pos, page_table=table,
            page_size=page, page_write_ok=ok,
        )
    )
    for b, seq in enumerate(seqs):
        prompt = len(seq) - 6
        for off in range(0, prompt, piece):
            ids = np.zeros((1, piece), np.int32)
            n = min(piece, prompt - off)
            ids[0, :n] = seq[off:off + n]
            logits, cache = apply(
                cache, jnp.asarray(ids), jnp.asarray(off + np.arange(piece)[None]),
                jnp.asarray(np.arange(piece)[None] < n), jnp.asarray(table[b:b + 1]),
            )
            out[b][off:off + n] = np.asarray(logits[0, :n])
    for step in range(6):
        pos = np.array([len(s) - 6 + step for s in seqs])
        tok = np.array([s[p] for s, p in zip(seqs, pos)], np.int32)
        logits, cache = apply(
            cache, jnp.asarray(tok[:, None]), jnp.asarray(pos[:, None]),
            jnp.ones((B, 1), bool), jnp.asarray(table),
        )
        for b, p in enumerate(pos):
            out[b][p] = np.asarray(logits[b, 0])
    return out


@pytest.mark.parametrize("interpret", [False, True], ids=["gather", "paged_kernel"])
def test_prefill_in_pieces_then_decode_matches_the_full_forward(params, tokens, interpret):
    """On logits, at every position: prompts of 34, 25 and 13 tokens in
    pieces of 16 (three, two and one), then six decode steps of the three
    rows together, against the reference's one pass over each sequence —
    under the gather and under the interpreter's paged kernel, the window
    layers passing 8 and the global layer none."""
    seqs = [tokens[0][:40], tokens[1][:31], tokens[2][:19]]
    got = paged_logits(program(interpret), params, seqs)
    for seq, logits in zip(seqs, got):
        want = reference_logits(params, seq)
        np.testing.assert_allclose(logits, want, atol=1e-4 * want.std())


@pytest.mark.parametrize("window", [8, 100], ids=["window8", "window100"])
def test_flash_read_pieces_match_the_full_forward(params, window):
    """128-token pieces — whole q blocks, so under the interpreter each
    one gathers its row's window and attends through the flash forward
    kernel (`paged_flash_read`) — of prompts of 230 and 150 tokens: a
    second piece at offset 128, last pieces with 26 and 106 pad
    positions, window layers (8 keys; 100, which crosses the pieces'
    boundary) beside the global one, then six decode steps through the
    paged kernel; against the reference's one pass over each sequence."""
    from kubeflow_tpu.models.transformer import paged_flash_read

    cfg = dict(CFG, sliding_window=window)
    pc = afmoe.program_config(cfg, attn_impl="reference", interpret_kernels=True)
    assert paged_flash_read(pc, 128)
    rng = np.random.default_rng(3)
    seqs = [rng.integers(2, CFG["vocab_size"], size=n).astype(np.int32)
            for n in (236, 156)]
    got = paged_logits(pc, params, seqs, piece=128, page=16)
    for seq, logits in zip(seqs, got):
        want = np.asarray(
            afmoe.reference_logits(params, seq, np.arange(len(seq)), cfg)
        )
        np.testing.assert_allclose(logits, want, atol=1e-4 * want.std())


def serve(pc, params, prompts, new=14):
    """The engine itself: prompts of unequal length admitted at different
    times, prefilled in pieces, decoded through the paged pool."""
    engine = LMEngine(
        TransformerLM(pc), pc, params, max_batch=4, max_seq=64, prefill_chunk=16,
        page_size=16, eos_id=10_000, chunk_steps=4,
    ).start()
    outs = [None] * len(prompts)

    def one(i):
        outs[i] = engine.submit(list(map(int, prompts[i])), max_new_tokens=new)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(prompts))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        engine.stop()
    return outs, engine


def regrets(params, prompts, outs):
    """How far the reference's logit of each served token lies below that
    position's best, in standard deviations of the position's logits."""
    out = []
    for prompt, served in zip(prompts, outs):
        seq = list(map(int, prompt)) + list(served)
        rows = np.arange(len(prompt) - 1, len(seq) - 1)
        logits = reference_logits(params, seq, rows)
        chosen = logits[np.arange(len(rows)), np.asarray(served)]
        out.append((logits.max(axis=1) - chosen) / logits.std(axis=1))
    return np.concatenate(out)


#: five prompts for four rows: the fifth waits for a row and joins a batch
#: in flight; 37 and 29 tokens prefill in three and two pieces
PROMPT_LENGTHS = (37, 9, 21, 29, 16)
#: float32 on both sides: a served token is the reference's choice
REGRET_TOLERANCE = 1e-3


@pytest.mark.parametrize("interpret", [False, True], ids=["gather", "paged_kernel"])
def test_the_engine_serves_what_the_reference_computes(params, tokens, interpret):
    prompts = [tokens[i][:n] for i, n in enumerate(PROMPT_LENGTHS)]
    outs, engine = serve(program(interpret), params, prompts)
    assert engine.kernel_read == interpret
    assert [len(o) for o in outs] == [14] * 5
    assert regrets(params, prompts, outs).max() <= REGRET_TOLERANCE
    stats = engine.stats
    assert stats["prefill_pieces"] == 3 + 1 + 2 + 2 + 1
    # every live token is 4 assignments in each of the 4 expert layers
    assert stats["moe_assignments_prefill"] == 16 * sum(PROMPT_LENGTHS)
    assert stats["moe_assignments_decode"] == 16 * 5 * 13
    assert engine.moe_expert_load.sum() == 16 * (sum(PROMPT_LENGTHS) + 5 * 13)
    assert 0 < stats["moe_experts_touched_decode"] <= 16 * stats["moe_layer_steps_decode"]
    assert stats["moe_load_max_decode"] >= stats["moe_layer_steps_decode"]
    # rows past the window of 8 hold pages the four window layers no longer read
    assert 0 < stats["kv_pages_dead_window"] < stats["kv_pages_held"]


# (d) planted faults -------------------------------------------------------- #

def _kinds(**change):
    """The pattern with one field of the last (global) layer, or of every
    window layer, changed."""
    kinds = list(program().layer_kinds)
    if "global_layer" in change:
        kinds[-1] = dataclasses.replace(kinds[-1], **change["global_layer"])
    else:
        kinds = [
            dataclasses.replace(k, **change["window_layers"]) if k.window else k
            for k in kinds
        ]
    return tuple(kinds)


FAULTS = {
    "the window applied to the global layer": dict(layer_kinds=_kinds(global_layer={"window": 8})),
    "rope on the global layer": dict(layer_kinds=_kinds(global_layer={"rope": True})),
    "the chosen weights not normalised": "route_norm",
    "the route scale dropped": "route_scale",
    "the output gate dropped": dict(attn_gate=False),
    "a post-norm dropped": dict(sandwich_norm=False),
    "the embedding scale dropped": dict(embed_scale=False),
    "a window one key short": dict(layer_kinds=_kinds(window_layers={"window": 7})),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_engines_tolerance(params, tokens, fault):
    """The same comparison with one term of the mathematics wrong in the
    program: each is caught (the unused parameters stay in the tree: a
    module that does not ask for them ignores them)."""
    change = FAULTS[fault]
    if change == "route_norm":
        change = dict(moe=dataclasses.replace(program().moe, route_norm=False))
    elif change == "route_scale":
        change = dict(moe=dataclasses.replace(program().moe, route_scale=1.0))
    prompts = [tokens[i][:n] for i, n in enumerate(PROMPT_LENGTHS)]
    outs, _ = serve(program(**change), params, prompts, new=24)
    assert regrets(params, prompts, outs).max() > 50 * REGRET_TOLERANCE


# (c) no neighbour dependence ----------------------------------------------- #

def skewed(params):
    """The selection bias pushed so that every token of every layer takes
    expert 3 first: the load a capacity would cut."""
    out = jax.tree_util.tree_map(lambda x: x, params)
    for name, layer in out.items():
        if "experts" in layer:
            bias = layer["experts"]["router"]["bias"]
            layer["experts"]["router"]["bias"] = bias.at[3].set(1.0)
    return out


def test_a_rows_logits_do_not_depend_on_its_neighbours(params, tokens):
    """Served alone and beside seven others under skewed routing, a row's
    logits agree to float32 rounding; the capacity path, given the same
    rows, differs by whole terms (tokens past an expert's buffer lose it)."""
    p = skewed(params)
    model = TransformerLM(program())
    alone = np.asarray(model.apply({"params": p}, tokens[:1]))[0]
    beside = np.asarray(model.apply({"params": p}, tokens))[0]
    spread = alone.std()
    assert np.abs(alone - beside).max() <= 1e-5 * spread
    # it is the reference's too
    np.testing.assert_allclose(alone, reference_logits(p, tokens[0]), atol=1e-4 * spread)
    layer = p["layers_1"]["experts"]
    x = jax.random.normal(jax.random.PRNGKey(1), (8 * SEQ, 64))
    cap = MoEConfig(num_experts=16, expert_dim=48, top_k=4)
    args = (layer["router"]["kernel"], layer["up_proj"]["kernel"], layer["down_proj"]["kernel"])
    one = moe_ffn(x[:SEQ], *args, cap)[0]
    eight = moe_ffn(x, *args, cap)[0][:SEQ]
    assert np.abs(np.asarray(one - eight)).max() > 1e-2 * float(one.std())


# (e) the grouped dispatch --------------------------------------------------- #

def every_expert_masked(x, layer, cfg, first=0, count=None):
    """Every held expert on every token, then the routing as a mask."""
    count = cfg.num_experts if count is None else count
    experts, weights = route(x, layer["router"]["kernel"], layer["router"]["bias"], cfg)
    k = lambda name: layer[name]["kernel"][first:first + count]
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, k("gate_proj"))) * jnp.einsum(
        "td,edf->etf", x, k("up_proj"))
    y = jnp.einsum("etf,efd->etd", h, k("down_proj"))
    chosen = experts[None] == (first + jnp.arange(count))[:, None, None]     # (e, t, k)
    return jnp.einsum("etd,et->td", y, (chosen * weights[None]).sum(-1)), experts


@pytest.mark.parametrize("interpret", [False, True], ids=["ragged_dot", "gmm_kernel"])
def test_grouped_dispatch_is_the_masked_sum_over_every_expert(params, interpret):
    """With an expert that gets every token and experts that get none."""
    cfg = program().moe
    layer = jax.tree_util.tree_map(lambda x: x, params["layers_2"]["experts"])
    bias = jnp.zeros((16,)).at[0].set(2.0).at[10:].set(-2.0)
    layer["router"]["bias"] = bias
    x = jax.random.normal(jax.random.PRNGKey(2), (50, 64))
    want, experts = every_expert_masked(x, layer, cfg)
    loads = np.bincount(np.asarray(experts).reshape(-1), minlength=16)
    assert loads[0] == 50 and not loads[10:].any()
    live = jnp.arange(50) < 30
    got, counts = dropless_moe_ffn(
        x, layer["router"]["kernel"], bias, layer["gate_proj"]["kernel"],
        layer["up_proj"]["kernel"], layer["down_proj"]["kernel"], cfg,
        live=live, interpret=interpret,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5 * float(want.std()))
    # the counters read the live tokens only
    assert np.array_equal(
        np.asarray(counts), np.bincount(np.asarray(experts[:30]).reshape(-1), minlength=16))


# (f) the chip's share ------------------------------------------------------- #

def test_eight_shares_of_two_experts_add_up_to_the_uncut_layer(params):
    """Each share routes over all 16, computes its own two experts' part
    and leaves the rest out; what every chip computes alike — the shared
    expert — is counted once."""
    full_cfg = program()
    x = jax.random.normal(jax.random.PRNGKey(3), (24, 64))
    layer = params["layers_3"]["experts"]
    from kubeflow_tpu.models.transformer import Experts

    full = Experts(full_cfg).apply({"params": layer}, x)
    shared = full - dropless_moe_ffn(
        x, layer["router"]["kernel"], layer["router"]["bias"], layer["gate_proj"]["kernel"],
        layer["up_proj"]["kernel"], layer["down_proj"]["kernel"], full_cfg.moe,
    )[0]
    total = jnp.zeros_like(full)
    counted = np.zeros(16, np.int64)
    for share in range(8):
        moe = dataclasses.replace(full_cfg.moe, first_expert=2 * share, held_experts=2)
        held = jax.tree_util.tree_map(lambda x: x, layer)
        for name in ("gate_proj", "up_proj", "down_proj"):
            held[name] = {"kernel": layer[name]["kernel"][2 * share:2 * share + 2]}
        out, state = Experts(dataclasses.replace(full_cfg, moe=moe)).apply(
            {"params": held}, x, mutable=["moe_stats"])
        total = total + out - shared
        counted += np.asarray(state["moe_stats"]["assignments"][0])
        want, _ = every_expert_masked(x, layer, full_cfg.moe, 2 * share, 2)
        np.testing.assert_allclose(np.asarray(out - shared), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(full), atol=1e-5)
    assert counted.sum() == 24 * 4      # each assignment counted by the one share that holds it


# configuration ------------------------------------------------------------- #

def test_a_uniform_model_is_the_pattern_of_one_kind():
    cfg = TransformerConfig(n_layers=3, attn_window=16)
    assert cfg.kinds == (LayerKind(window=16),) * 3 and cfg.moe_layers == 0
    assert TransformerConfig(d_model=256, n_heads=8).head_dim == 32
    assert TransformerConfig(d_model=256, n_heads=8, d_head=128).head_dim == 128
    kinds = moe_every_kinds(4, 2)
    assert [k.ffn for k in kinds] == ["dense", "moe", "dense", "moe"]
    assert TransformerConfig(n_layers=4, layer_kinds=kinds).moe_layers == 2


@pytest.mark.parametrize("bad,match", [
    (dict(n_layers=2, layer_kinds=(LayerKind(),)), "layer_kinds has 1 entries"),
    (dict(n_layers=1, layer_kinds=(LayerKind(),), attn_window=8), "each kind carries its own"),
    (dict(n_layers=1, layer_kinds=(LayerKind(ffn="conv"),)), "not dense/moe"),
    (dict(n_layers=1, use_rope=False, layer_kinds=(LayerKind(rope=True),)), "needs use_rope"),
    (dict(n_layers=1, layer_kinds=(LayerKind(ffn="moe"),),
          moe=MoEConfig(score_func="sigmoid")), "capacity path routes by softmax"),
    (dict(n_layers=1, layer_kinds=(LayerKind(ffn="moe"),),
          moe=MoEConfig(expert_form="gated_silu")), "two-matrix GELU form"),
    (dict(n_layers=1, layer_kinds=(LayerKind(ffn="moe"),),
          moe=MoEConfig(capacity_factor=None, first_expert=6, held_experts=4)), "not among the 8"),
])
def test_a_pattern_that_cannot_run_is_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**bad).validate()


# the gather read of a window layer ------------------------------------------ #

@pytest.mark.parametrize("span", [1, 16], ids=["decode", "piece"])
def test_a_window_layers_gather_reads_its_reach_only(span):
    """Rows at the table's start, its middle and its very end (where the
    slab is pushed back inside the table), window 24 on 16-token pages of
    a 12-page table: the gather of the 4 or 5 pages the span's windows
    reach is the gather of all 12, masked."""
    from kubeflow_tpu.models.transformer import paged_gather_attention

    rng = np.random.default_rng(span)
    P, pages, B, H, Hkv, D, window = 16, 12, 4, 4, 2, 8, 24
    cache = {
        name: jnp.asarray(rng.normal(size=((1 + B * pages) * P, Hkv, D)), jnp.float32)
        for name in ("k", "v")
    }
    table = jnp.asarray(1 + rng.permutation(B * pages).reshape(B, pages).astype(np.int32))
    pos0 = np.array([0, 70, 101, pages * P - span])
    positions = jnp.asarray(pos0[:, None] + np.arange(span)[None])
    q = jnp.asarray(rng.normal(size=(B, H, span, D)), jnp.float32)
    got = paged_gather_attention(q, cache, table, positions, page_size=P, window=window)
    # the same keys through a table too narrow to cut: the old read
    flat = (np.asarray(table)[:, :, None] * P + np.arange(P)).reshape(B, pages * P)
    K = np.asarray(cache["k"])[flat].transpose(0, 2, 1, 3)       # (B, Hkv, W, D)
    V = np.asarray(cache["v"])[flat].transpose(0, 2, 1, 3)
    kpos = np.arange(pages * P)
    qpos = np.asarray(positions)
    mask = (kpos[None, None] <= qpos[:, :, None]) & (kpos[None, None] > qpos[:, :, None] - window)
    qg = np.asarray(q).reshape(B, Hkv, H // Hkv, span, D)
    scores = np.einsum("bhgsd,bhtd->bhgst", qg, K) / np.sqrt(D)
    scores = np.where(mask[:, None, None], scores, -1e30)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.einsum("bhgst,bhtd->bhgsd", probs, V).reshape(B, H, span, D)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
