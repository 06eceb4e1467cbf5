"""Each runner kind end to end at a tiny size on the CPU, calling the runner
functions directly (the command itself refuses a CPU). These show control
flow, counts and correctness checks — never a speed."""

import json
import subprocess
import sys
import time

import jax
import pytest

from benchmark.manifest import ROOT, Manifest, plugin
from benchmark.run import collect_metrics, result_line
from benchmark.runners import RunContext, serve_common

#: the peaks table wants a known kind; no device number is read from it here
FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
TINY_DECODER = dict(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=128, vocab_size=512, sliding_window=48,
    activation_dtype="float32", weight_dtype="float32",
)


#: a handful of steps: a long run-ahead of eight-device steps on a loaded CPU
#: backend can time out its collectives' rendezvous and abort the worker
TRAIN_SECONDS = 0.03


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


def context(manifest, cell, cfg, mix, *, seconds, trace=False):
    return RunContext(
        cell=cell, config=cfg, traffic=mix, seed=3, seconds=seconds,
        trace=trace, device=FAKE_DEVICE, t_process=time.perf_counter(),
        profile_device=False,   # the CPU backend has no device plane
    )


def run(ctx):
    return plugin("runners", ctx.traffic["runner"]).run(ctx)


def test_train_fit_bert(manifest):
    chips = jax.device_count()
    cell = dict(manifest.cell("bert-base_mlm-s512"), chips=chips)
    cfg = dict(
        manifest.config("bert-base"), hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128, vocab_size=512,
        max_position_embeddings=64, activation_dtype="float32",
    )
    # the CPU has no flash kernel
    cfg["train"] = dict(cfg["train"], program={"attn_impl": "reference"})
    mix = dict(manifest.traffic("mlm-s512"), seq_len=32, global_batch=chips, warm_steps=3)
    ev = run(context(manifest, cell, cfg, mix, seconds=TRAIN_SECONDS))
    assert ev.correct and ev.failed == 0 and ev.attempted >= 3
    check = ev.notes["check"]
    # float32 on both sides: every position agrees to rounding
    assert check["positions"] == 32 * chips and check["nll_err_max"] < 1e-4
    assert check["loss_rel_err"] < 1e-5
    assert ev.numbers["xla.compiles_in_window"] == 0
    assert ev.numbers["context.tokens_per_step"] == 32 * chips
    assert len(ev.hook_steps) == ev.attempted - 1
    got = collect_metrics(manifest, ev, traced=False)
    assert set(got) == {"tokens_per_s", "setup_s"}
    layers = collect_metrics(manifest, ev, traced=True)
    assert {"trainer_step_ms_p50", "trainer_data_stall_ms", "model_mfu",
            "programs_compiled", "compiles_in_window"} <= set(layers)
    # no device trace was taken: its readers found nothing and are left out
    assert "device_idle_share" not in layers and "attn_kernel_time_share" not in layers
    line = result_line(manifest, ev, FAKE_DEVICE, traced=False)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(line)


def test_train_fit_sharded_decoder_matches_the_plain_reference(manifest):
    chips = jax.device_count()
    assert chips == 8
    cell = dict(manifest.cell("mistral-7b_pretrain-x4"), chips=chips)
    cfg = dict(manifest.config("mistral-7b-l12-x4"), **TINY_DECODER)
    cfg["sliding_window"] = 16
    cfg["train"] = dict(cfg["train"], mesh={"data": 2, "fsdp": 2, "model": 2},
                        program=dict(cfg["train"]["program"], attn_impl="reference"))
    mix = dict(manifest.traffic("pretrain-x4"), seq_len=32, global_batch=8, warm_steps=3)
    ev = run(context(manifest, cell, cfg, mix, seconds=TRAIN_SECONDS))
    assert ev.correct
    # the window of 16 bites at S32, under remat and the one-hot embedding
    assert ev.notes["check"]["nll_err_max"] < 1e-4 and ev.notes["check"]["loss_rel_err"] < 1e-5
    assert set(collect_metrics(manifest, ev, traced=False)) == {"tokens_per_s", "setup_s"}


def zero_o_proj(params):
    """The attention term dropped: every output projection zeroed."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * 0 if any(getattr(k, "key", None) == "o_proj" for k in path) else x,
        params)


@pytest.mark.parametrize("family,fault", [
    ("mistral", None), ("mistral", "attention dropped"), ("mistral", "no window"),
    ("mistral", "window one key short"), ("mistral", "not causal"),
    ("bert", None), ("bert", "attention dropped"),
])
def test_the_training_check_fails_wrong_mathematics(manifest, family, fault):
    """``check_first_batch`` with the limits of the real cells: the program's
    forward passes; one with a fault a speed-up could introduce does not —
    while the scalar loss the check used to rest on cannot tell them apart
    (on seeded weights every logit is about N(0, 1) whatever the layers do)."""
    import types

    from jax.sharding import NamedSharding
    from kubeflow_tpu.core.mesh import MeshSpec, build_mesh
    from kubeflow_tpu.models.transformer import TransformerLM
    from kubeflow_tpu.train.loop import BATCH_SPEC

    from benchmark.runners import train_fit

    name, mixname = {"mistral": ("mistral-7b-l12-x4", "pretrain-x4"),
                     "bert": ("bert-base", "mlm-s512")}[family]
    cfg = dict(manifest.config(name), **dict(TINY_DECODER, sliding_window=16, max_position_embeddings=64))
    program = dict(cfg["train"].get("program", {}), attn_impl="reference")
    cfg["train"] = dict(cfg["train"], mesh={"data": jax.device_count()}, program=program)
    mix = dict(manifest.traffic(mixname), seq_len=32, global_batch=8)
    fam = plugin("families", family)
    setup = fam.train_setup(cfg, mix, seed=3)
    mesh = build_mesh(MeshSpec.data_parallel(jax.device_count()))
    trainer = types.SimpleNamespace(mesh=mesh, batch_sharding=NamedSharding(mesh, BATCH_SPEC))
    params = setup["init_params"](jax.random.PRNGKey(0))
    batch0, rng0 = next(iter(setup["data"](0))), jax.random.PRNGKey(1)
    loss = float(setup["loss_fn"](params, batch0, rng0)[0])

    forward = setup["forward"]
    if fault == "attention dropped":
        setup["forward"] = lambda p, x: forward(zero_o_proj(p), x)
    elif fault is not None:
        kw = {"no window": dict(attn_window=None), "window one key short": dict(attn_window=15),
              "not causal": dict(attn_window=None, causal=False)}[fault]
        wrong = TransformerLM(fam.program_config(cfg, **dict(program, **kw)))
        setup["forward"] = lambda p, x: wrong.apply({"params": p}, x)
    check = train_fit.check_first_batch(setup, trainer, params, batch0, rng0, loss, mix["check"])
    assert check["positions"] == 8 * 32 and check["loss_rel_err"] < 1e-5
    if fault is None:
        assert check["ok"] and check["nll_err_max"] < 1e-4
    else:
        # single positions move by whole units: over the limit, which is ten
        # times what bf16 rounding moves them on the chip
        assert not check["ok"]
        assert check["nll_err_max"] > 1.0 > mix["check"]["nll_err_max"]


def serve_config(manifest):
    cfg = dict(manifest.config("mistral-7b-l16"), **TINY_DECODER)
    cfg["serve"] = dict(cfg["serve"], max_batch=4, max_seq=160, page_size=16,
                        prefill_chunk=32, kv_pool_tokens=640, max_new_tokens=32)
    return cfg


def test_serve_closed(manifest):
    cell = manifest.cell("mistral-7b_gen-closed")
    mix = dict(
        manifest.traffic("gen-closed"), clients=6, ramp_s=0.5, max_requests_per_s=400,
        prompt_tokens={"dist": "lognormal", "median": 24, "sigma": 0.6, "min": 8, "max": 64},
        output_tokens={"dist": "lognormal", "median": 16, "sigma": 0.5, "min": 8, "max": 32},
    )
    ev = run(context(manifest, cell, serve_config(manifest), mix, seconds=1.5))
    assert ev.correct and ev.failed == 0 and ev.attempted > 10
    assert ev.notes["check"]["ok"] and ev.notes["check"]["tokens_checked"] >= 24
    # float32 on both sides: the engine's token is the reference's choice
    assert ev.notes["check"]["regret_max"] <= 1e-3
    assert ev.numbers["xla.compiles_in_window"] == 0
    assert ev.numbers["engine.chunks"] > 0 and ev.numbers["client.output_tokens"] > 0
    got = collect_metrics(manifest, ev, traced=False)
    # a saturated closed loop is judged on throughput; its tails swing
    assert set(got) == {"output_tokens_per_s", "setup_s"}
    assert got["output_tokens_per_s"]["value"] == pytest.approx(
        ev.numbers["client.output_tokens"] / 1.5)
    layers = collect_metrics(manifest, ev, traced=True)
    assert 0 < layers["engine_batch_occupancy"]["value"] <= 100
    assert layers["closed_ttft_ms_p50"]["value"] > 0 and layers["closed_tpot_ms_p90"]["value"] > 0
    # nothing was traced in this run: span readers found nothing
    assert "engine_decode_step_ms" not in layers and "engine_prefill_ms_p50" not in layers
    # one reading, two entries: the variant is read by the stem's file
    assert manifest.layer_metric("hbm_peak_gib.closed") == manifest.layer_metric("hbm_peak_gib")
    assert "hbm_peak_gib.closed" in layers and "hbm_peak_gib" not in layers


def test_serve_open_traced(manifest):
    """No cell is open-loop yet (PERF.md, first open question); the runner
    and the mix ISSUE 22 specified are kept for the one that will be."""
    cell = {"name": "tiny_docqa-open", "config": "mistral-7b-l16", "traffic": "docqa-open", "chips": 1}
    mix = dict(
        manifest.traffic("docqa-open"), ramp_s=0.5, drain_s=5.0,
        arrivals={"process": "poisson", "rate_rps": 20.0},
        prompt_tokens={"dist": "lognormal", "median": 60, "sigma": 0.6, "min": 24, "max": 128},
        output_tokens={"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 8, "max": 24},
    )
    ev = run(context(manifest, cell, serve_config(manifest), mix, seconds=1.5, trace=True))
    assert ev.correct and ev.failed == 0 and ev.attempted > 10
    # the longest request is checked, and it is longer than the window of 48
    assert max(ev.notes["check"]["lengths"]) > 48
    assert all(s.trace_id for s in ev.samples) and len(ev.traces) >= ev.attempted
    assert all(s.ttft_s >= s.ttft_from_send_s for s in ev.samples)
    n = ev.numbers
    assert n["e2e.ttft_p50_ms"] <= n["e2e.ttft_p90_ms"] and n["e2e.tpot_p50_ms"] <= n["e2e.tpot_p90_ms"]
    assert sum(ev.notes["tokens_by_second"]) == n["client.output_tokens"]
    assert 0 <= ev.notes["token_silence_s_max"] < 1.5
    # every request was traced: the span readers find the engine's stages
    from benchmark.readers import span
    assert span.read({"span": "prefill"}, ev) > 0
    assert span.read({"span": "decode.chunk", "per_number": "context.chunk_steps"}, ev) > 0
    assert span.read({"span": "decode.chunk", "per_number": "context.nope"}, ev) is None
    assert span.read({"span": "no.such.span"}, ev) is None


def test_warm_plan_reaches_every_program_of_the_mix(manifest):
    serve = manifest.config("mistral-7b-l16")["serve"]
    page, cap = serve["page_size"], -(-serve["max_seq"] // serve["page_size"])

    def pages_w(tokens):   # the engine's rule: pow2 pages, capped
        need, w = -(-tokens // page), 1
        while w < need:
            w *= 2
        return min(w, cap)

    def chunk_widths(prompt, new, span=8):
        """Every table width the engine picks while one request decodes:
        min(tokens so far + one chunk, prompt + budget), chunk by chunk."""
        out, tokens = set(), prompt + 1
        while tokens < prompt + new:
            out.add(pages_w(min(tokens + span, prompt + new)))
            tokens += span
        return out

    tiny = {"prompt_tokens": {"dist": "lognormal", "median": 60, "sigma": 0.6, "min": 24, "max": 128},
            "output_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 8, "max": 24}}
    for mix in (manifest.traffic("docqa-open"), manifest.traffic("gen-closed"), tiny):
        plan = serve_common.warm_plan(mix, serve, pages_w)
        p_lo, p_hi = serve_common.length_bounds(mix["prompt_tokens"])
        o_lo, o_hi = serve_common.length_bounds(mix["output_tokens"])
        warmed = set().union(*(chunk_widths(p, n) for p, n in plan))
        corners = [(p, o) for p in (p_lo, p_hi) for o in (o_lo, o_hi)]
        reachable = set().union(*(chunk_widths(p, o) for p, o in corners))
        reachable |= {pages_w(t) for t in range(p_lo + min(o_lo, 9), p_hi + o_hi + 1)}
        assert reachable <= warmed, mix
        assert all(p_lo <= p <= p_hi and n >= 2 and p + n <= serve["max_seq"] for p, n in plan)
        assert max(p for p, _ in plan) == p_hi      # every prefill piece's program


def test_the_command_refuses_a_cpu():
    """Exit code other than 0 and no result line, naming what JAX found."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "bert-base_mlm-s512",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 3
    assert proc.stdout.strip() == ""
    assert "needs 1 TPU chip(s)" in proc.stderr and "'cpu'" in proc.stderr
