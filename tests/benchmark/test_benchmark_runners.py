"""Each runner kind end to end at a tiny size on the CPU, calling the runner
functions directly (the command itself refuses a CPU). These show control
flow, counts and correctness checks — never a speed."""

import json
import subprocess
import sys
import time

import jax
import pytest

from benchmark.check import stderr_lines
from benchmark.manifest import ROOT, Manifest, plugin
from benchmark.run import collect_metrics, result_line
from benchmark.runners import RunContext, serve_common, train_fit

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
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "check"]
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    # what decided ``correct``, each number beside its limit, comes last
    limits = dict(mix["check"], loss_rel_err=train_fit.LOSS_RTOL)
    for name in ("nll_err_max", "nll_err_mean", "loss_rel_err"):
        assert line["check"][name] == check[name] <= line["check"][f"{name}_limit"] == limits[name]
    assert line["check"]["positions"] == 32 * chips
    assert line["check"]["compiles_in_window"] == line["check"]["losses_not_finite"] == 0
    assert all(isinstance(v, (int, float)) for v in line["check"].values())
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
    assert ev.notes["check"]["ok"] and ev.check["tokens_checked"] >= 24
    assert len(ev.notes["check"]["requests"]) == mix["check"]["requests"] == 3
    # float32 on both sides: the engine's token is the reference's choice
    assert ev.check["regret_max"] <= 1e-3
    # the last line carries what decided ``correct``, each beside its limit
    line = result_line(manifest, ev, FAKE_DEVICE, traced=False)
    assert list(line)[-1] == "check" and line["check"] == ev.check
    assert {k: v for k, v in ev.check.items() if k.endswith("_limit")} == {
        "regret_max_limit": 0.2, "regret_mean_limit": 0.004, "compiles_in_window_limit": 0}
    assert {"regret_p99", "argmax_share", "compiles_in_window"} <= set(ev.check)
    assert "check: regret_mean " in "\n".join(stderr_lines(ev.check))
    assert ev.numbers["xla.compiles_in_window"] == 0
    assert ev.numbers["engine.chunks"] > 0 and ev.numbers["client.output_tokens"] > 0
    got = collect_metrics(manifest, ev, traced=False)
    # a saturated closed loop is judged on throughput; its tails swing
    assert set(got) == {"output_tokens_per_s", "setup_s"}
    assert got["output_tokens_per_s"]["value"] == pytest.approx(
        ev.numbers["client.output_tokens"] / 1.5)
    layers = collect_metrics(manifest, ev, traced=True)
    assert 0 < layers["engine_batch_occupancy"]["value"] <= 100
    # the whole step's share: tokens the engine computed x the family's count
    n = ev.numbers
    assert n["serve.forward_tokens"] == (
        n["engine.prefill_tokens"] + n["client.output_tokens"] - n["client.first_tokens"]) > 0
    assert n["context.forward_flops_per_token"] == plugin("families", "mistral").serve_context(
        serve_config(manifest), mix, None)["forward_flops_per_token"]
    assert 0 < layers["serve_model_mfu"]["value"] <= 100
    assert layers["serve_model_mfu"]["value"] == pytest.approx(
        100 * n["serve.forward_tokens"] * n["context.forward_flops_per_token"] / (1.5 * 197e12))
    assert layers["closed_ttft_ms_p50"]["value"] > 0 and layers["closed_tpot_ms_p90"]["value"] > 0
    # nothing was traced in this run: span readers found nothing
    assert "engine_decode_step_ms" not in layers and "engine_prefill_ms_p50" not in layers
    # one reading, two entries: the variant is read by the stem's file
    assert manifest.layer_metric("hbm_peak_gib.closed") == manifest.layer_metric("hbm_peak_gib")
    assert "hbm_peak_gib.closed" in layers and "hbm_peak_gib" not in layers


def test_closed_loop_callers_take_the_next_unsent_request():
    """Six callers, forty requests, and a stub endpoint whose reply time
    varies by request, the first by far the slowest: the requests go out in
    index order, each once, and the slow one holds back none after it —
    where callers that each own every sixth request would leave 6, 12, …
    waiting for it."""
    import asyncio
    import threading

    from aiohttp import web

    from benchmark.traffic import Request

    received: list[int] = []

    async def generate(request):
        i = (await request.json())["input_ids"][0]
        received.append(i)
        resp = web.StreamResponse()
        await resp.prepare(request)
        await asyncio.sleep(2.0 if i == 0 else 0.005 * (1 + i % 7))
        await resp.write(b"data: " + json.dumps({"token_ids": [i]}).encode() + b"\n\n")
        await resp.write(b'data: {"done": true}\n\n')
        await resp.write_eof()
        return resp

    app = web.Application()
    app.router.add_post("/generate_stream", generate)
    runner = web.AppRunner(app)
    loop = asyncio.new_event_loop()
    loop.run_until_complete(runner.setup())
    site = web.TCPSite(runner, "127.0.0.1", 0)
    loop.run_until_complete(site.start())
    port = site._server.sockets[0].getsockname()[1]
    server = threading.Thread(target=loop.run_forever, name="stub-endpoint")
    server.start()
    requests = [Request(index=i, prompt=(i,), max_new_tokens=1) for i in range(40)]
    client = serve_common.Client(
        f"http://127.0.0.1:{port}/generate_stream", requests,
        mode="closed", clients=6, traced=False, seed=3,
    )
    try:
        client.start()
        deadline = time.monotonic() + 60
        while any(s.t_done is None for s in client.samples) and time.monotonic() < deadline:
            time.sleep(0.01)
        client.stop()
    finally:
        asyncio.run_coroutine_threadsafe(runner.cleanup(), loop).result(timeout=30)
        loop.call_soon_threadsafe(loop.stop)
        server.join(timeout=30)
        loop.close()
    assert not server.is_alive()
    samples = client.samples
    assert all(s.ok for s in samples), [s.error for s in samples if not s.ok]
    assert sorted(received) == list(range(40))
    assert sorted(range(40), key=lambda i: samples[i].t_sent) == list(range(40))
    assert all(samples[i].t_due <= samples[i].t_sent for i in range(40))
    # every other request went out and came back while the first was served
    assert max(samples[i].t_done for i in range(1, 40)) < samples[0].t_done


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
    assert ev.check["regret_max_limit"] == 0.2 and ev.check["argmax_share"] > 0.9
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


def scratch_family(monkeypatch, name="scratchfam", **members):
    """A family module a later PR would add as ``benchmark/families/<name>.py``:
    here an object in ``sys.modules``, which is where ``plugin`` finds one.
    Everything it does not define is ``families/mistral.py``'s."""
    import types

    from benchmark.families import mistral

    module = types.ModuleType(f"benchmark.families.{name}")
    module.__dict__.update({k: v for k, v in vars(mistral).items() if not k.startswith("__")})
    module.__dict__.update(members)
    monkeypatch.setitem(sys.modules, module.__name__, module)
    return name


LONG_MIX = dict(
    clients=6, ramp_s=0.5, max_requests_per_s=400,
    prompt_tokens={"dist": "lognormal", "median": 24, "sigma": 0.6, "min": 8, "max": 64},
    output_tokens={"dist": "lognormal", "median": 40, "sigma": 0.3, "min": 32, "max": 64},
)


def test_a_scratch_family_and_a_p99_block_need_no_edit(manifest, monkeypatch):
    """What the next ``model_config`` PR brings by files alone: a family
    with a three-line ``serve_context`` (its counts reach the readers as
    ``context.<key>``) and a traffic file whose ``check`` holds a 99th
    percentile, through ``serve_closed`` as it stands."""
    def serve_context(cfg, mix, serve):
        return {"forward_flops_per_token": 2.0 * 12 * cfg["hidden_size"] ** 2,
                "latent_read_flops": 3.0e6, "latent_read_bytes": serve["page_size"] * 576.0}

    before = {p: p.read_bytes() for p in (ROOT / "benchmark").rglob("*.py")}
    cfg = dict(serve_config(manifest), family=scratch_family(monkeypatch, serve_context=serve_context))
    cfg["serve"] = dict(cfg["serve"], max_new_tokens=64)
    mix = dict(
        manifest.traffic("gen-closed"), **LONG_MIX,
        check={"requests": 40, "regret_p99": 0.01, "regret_mean": 0.001, "argmax_share_min": 0.95,
               "measured": {"regret_p99": 0.001, "regret_mean": 0.0001, "argmax_share_min": 0.995},
               "seeds": [3, 5, 7, 11, 13]},
        check_why="float32 on both sides on the CPU: every served token is the reference's choice",
    )
    ev = run(context(manifest, manifest.cell("mistral-7b_gen-closed"), cfg, mix, seconds=3.0))
    assert ev.correct, ev.notes["check"]
    assert ev.check["tokens_checked"] >= ev.check["tokens_checked_limit"] == 1000
    assert ev.check["regret_p99"] <= ev.check["regret_p99_limit"] == 0.01
    assert ev.check["argmax_share"] >= ev.check["argmax_share_limit"] == 0.95
    assert "regret_max_limit" not in ev.check and ev.check["regret_max"] <= 1e-3
    n = ev.numbers
    assert n["context.forward_flops_per_token"] == 2.0 * 12 * 64 ** 2
    assert n["context.latent_read_flops"] == 3.0e6 and n["context.latent_read_bytes"] == 16 * 576.0
    assert 0 < collect_metrics(manifest, ev, traced=True)["serve_model_mfu"]["value"] <= 100
    # the same run with fewer requests checked: the 99th percentile of fewer
    # than a thousand tokens is no reading, so the run is not correct
    few = serve_common.check_outputs(
        context(manifest, manifest.cell("mistral-7b_gen-closed"), cfg, mix, seconds=3.0),
        dict(mix["check"], requests=3), None, [], [])
    assert not few["ok"]
    assert {p: p.read_bytes() for p in before} == before, "an existing file was edited"


@pytest.mark.parametrize("fault", ["no window", "every token one id off"])
def test_a_broken_serving_path_is_not_correct(manifest, monkeypatch, fault):
    """The rest of a run with the timed path broken underneath — the model
    the engine serves has lost its sliding window, or every token is the id
    after the one computed, altered where the engine's programs choose it — ends with ``correct`` false and the
    numbers that say by how much on the result line."""
    from kubeflow_tpu.models.transformer import TransformerLM
    from kubeflow_tpu.serve import engine

    from benchmark.families import mistral

    cfg = serve_config(manifest)
    if fault == "no window":
        def serve_model(cfg):
            pc = mistral.program_config(cfg, attn_window=None)
            return TransformerLM(pc), pc

        cfg["family"] = scratch_family(monkeypatch, serve_model=serve_model)
    else:
        pick = engine._sample     # where the engine's programs choose a token
        monkeypatch.setattr(engine, "_sample", lambda *a, **kw: (pick(*a, **kw) + 1) % 512)
    cfg["serve"] = dict(cfg["serve"], max_new_tokens=64)
    # outputs of 32 and more on prompts of 24: well past the window of 48
    mix = dict(manifest.traffic("gen-closed"), **LONG_MIX)
    ev = run(context(manifest, manifest.cell("mistral-7b_gen-closed"), cfg, mix, seconds=2.0))
    assert ev.failed == 0 and ev.attempted > 3 and not ev.correct
    line = result_line(manifest, ev, FAKE_DEVICE, traced=False)
    assert line["correct"] is False and list(line)[-1] == "check"
    assert line["check"]["regret_max"] > 1.0 > line["check"]["regret_max_limit"]
    assert line["check"]["regret_mean"] > 10 * line["check"]["regret_mean_limit"]
    assert any(f.startswith("regret_max") for f in ev.notes["check"]["failed"])


def test_the_control_in_the_precision_below_is_not_correct(manifest):
    """``benchmark/control.py`` at a size a test holds: the configuration
    here states float32, so the control is the reference with bfloat16
    weights, rated at the served positions by the float32 reference. A
    block set from what float32 reads must fail it, while the program
    passes in the same run."""
    from benchmark import control

    cfg = serve_config(manifest)
    cfg["serve"] = dict(cfg["serve"], max_new_tokens=64)
    mix = dict(
        manifest.traffic("gen-closed"), **LONG_MIX,
        check={"requests": 12, "regret_max": 0.001, "regret_mean": 0.0001,
               "measured": {"regret_max": 0.0001, "regret_mean": 0.00001}, "seeds": [3, 5, 7, 11, 13]},
        check_why="float32 on both sides on the CPU: every served token is the reference's choice",
    )
    ctx = context(manifest, manifest.cell("mistral-7b_gen-closed"), cfg, mix, seconds=2.0)
    ev = serve_common.run(ctx, "closed", control=lambda p: control.Lowered(p, "float32"))
    check = ev.notes["check"]
    assert ev.correct and check["ok"] and check["numbers"]["regret_max"] <= 1e-4
    assert not check["control"]["ok"], check["control"]
    assert check["control"]["numbers"]["tokens_checked"] == check["numbers"]["tokens_checked"] > 400
    assert check["control"]["numbers"]["regret_max"] > 3 * mix["check"]["regret_max"]
    # the benchmark's own runs never run it
    assert "control" not in run(ctx).notes["check"]


def test_the_control_quantises_each_output_channel_and_leaves_vectors():
    import jax.numpy as jnp

    from benchmark import control

    rng = jax.random.PRNGKey(0)
    tree = {"embed": {"embedding": jax.random.normal(rng, (40, 16), jnp.bfloat16)},
            "layers_0": {"proj": {"kernel": jax.random.normal(rng, (16, 24), jnp.bfloat16)},
                         "ln": {"scale": jnp.full((16,), 1.5, jnp.bfloat16)}}}
    low = control.Lowered(tree, "bfloat16")
    assert set(low) == set(tree) and len(low) == 2
    k, w = low["layers_0"]["proj"]["kernel"], tree["layers_0"]["proj"]["kernel"].astype(jnp.float32)
    scale = jnp.abs(w).max(axis=0) / 127
    assert k.dtype == jnp.float32 and float(jnp.abs(k - w).max()) <= float(scale.max()) / 2 + 1e-6
    assert int(jnp.unique(jnp.round(k[:, 0] / scale[0])).size) <= 255
    assert not bool(jnp.array_equal(k, w))
    e, v = low["embed"]["embedding"], tree["embed"]["embedding"].astype(jnp.float32)
    assert float(jnp.abs(e - v).max(axis=1)[3]) <= float(jnp.abs(v[3]).max()) / 254 + 1e-6
    assert bool(jnp.array_equal(low["layers_0"]["ln"]["scale"], tree["layers_0"]["ln"]["scale"]))
    half = control.Lowered({"a": {"kernel": jnp.full((4, 4), 1.001, jnp.float32)}}, "float32")
    assert float(half["a"]["kernel"][0, 0]) == 1.0
    with pytest.raises(ValueError, match="no precision below"):
        control.Lowered({"a": {"kernel": jnp.ones((4, 4))}}, "int8")["a"]


@pytest.mark.parametrize("why,edit", [
    ("missing", lambda mix: mix.pop("check")),
    ("check_why", lambda mix: mix.pop("check_why")),
    ("unknown key", lambda mix: mix["check"].update(regret_p50=0.1)),
])
def test_a_mix_without_a_valid_check_sends_no_request(manifest, why, edit):
    mix = json.loads(json.dumps(manifest.traffic("gen-closed")))
    edit(mix)
    ctx = context(manifest, manifest.cell("mistral-7b_gen-closed"), serve_config(manifest), mix, seconds=1.0)
    t0 = time.perf_counter()
    with pytest.raises(ValueError, match=why):
        run(ctx)
    assert time.perf_counter() - t0 < 1.0     # no model was built


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
