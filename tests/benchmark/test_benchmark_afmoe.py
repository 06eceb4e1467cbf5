"""The `trinity-mini-l5` configuration, its cell and what PR 34 added to
read it: the files load through ``Manifest`` with no edit to a file that
was there, the family's counts equal a hand count, the plain reference
agrees with itself computed in blocks and whole, the new readers read a
recorded counter set, and the cell runs end to end at a tiny size on the
CPU (control flow and counts — never a speed)."""

import json
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, trace_reduce as tr
from benchmark.evidence import Evidence
from benchmark.families import afmoe
from benchmark.manifest import ROOT, Manifest, plugin
from benchmark.readers import moe_roofline
from benchmark.reference import afmoe as reference
from benchmark.run import collect_metrics, result_line
from benchmark.runners import RunContext
from benchmark.weights import seeded_params

CELL = "trinity-mini_mixed-closed"
FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
GEN_CLOSED = (
    "engine_decode_step_ms", "engine_prefill_ms_p50", "engine_batch_occupancy",
    "closed_ttft_ms_p50", "closed_tpot_ms_p90", "engine_decode_device_ms",
    "engine_prefill_device_ms_p50", "engine_prefill_device_share", "engine_prefill_pad_share",
    "engine_sched_blocked_share", "engine_sched_host_ms_per_chunk", "serve_model_mfu",
    "device_idle_share", "hbm_peak_gib",
)
NEW = (
    "moe_gmm_time_share", "moe_gmm_roofline", "moe_experts_touched_share",
    "moe_load_max_over_mean", "kv_window_dead_share",
)
#: hidden 64, 4 query / 2 KV heads of 32 (head_dim != hidden / heads), window
#: 8, one dense + s, s, s, f expert layers, 16 experts top-4 and a shared one
TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    sliding_window=8, intermediate_size=96, num_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=48, vocab_size=128, max_position_embeddings=256,
    experts_held={"first": 0, "count": 16},
    activation_dtype="float32", weight_dtype="float32",
)


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


def tiny_config(manifest, **over):
    return dict(manifest.config("trinity-mini-l5"), **TINY, **over)


def test_the_new_files_load_by_name_and_hold_the_published_widths(manifest):
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("trinity-mini-l5", "mixed-closed", 1)
    cfg = manifest.config(cell["config"])
    published = dict(
        hidden_size=2048, num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        intermediate_size=6144, moe_intermediate_size=1024, num_experts=128,
        num_experts_per_tok=8, num_shared_experts=1, sliding_window=2048, rope_theta=10000,
        rms_norm_eps=1e-5, route_scale=2.826, route_norm=True, score_func="sigmoid",
        vocab_size=200192, mup_enabled=True, max_position_embeddings=131072,
    )
    assert {k: cfg[k] for k in published} == published
    # every expert and the whole vocabulary held; the one cut is depth
    assert cfg["experts_held"] == {"first": 0, "count": 128}
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (5, 1)
    assert cfg["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"]) and cfg["deployment"]
    assert {"qk_norm", "attention_gate", "sandwich_norm", "rope", "expert_bias"} <= set(cfg["assumed"])
    mix = manifest.traffic(cell["traffic"])
    assert (mix["runner"], mix["clients"], mix["max_requests_per_s"]) == ("serve_closed", 128, 40)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1200, "sigma": 1.0, "min": 128, "max": 8192}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 192, "sigma": 0.5, "min": 64, "max": 512}
    assert check.validate(mix)["requests"] >= 8 and len(set(mix["check"]["seeds"])) >= 12
    # the callers fill every row and leave a queue behind them, inside
    # what the engine takes before it sheds (rows + its default queue of 64)
    assert 0 < mix["clients"] - cfg["serve"]["max_batch"] <= 64
    check_trinity_layers(manifest)
    assert [m["name"] for m in manifest.metrics_of(CELL, "end_to_end")] == [
        "output_tokens_per_s", "setup_s"]


def check_trinity_layers(manifest):
    """The cell reports PR 34's per-layer set, and beyond it only names
    entered after PR 34's entries; every one has a reader."""
    names = [m["name"] for m in manifest.metrics_of(CELL, "per_layer")]
    pr34 = {f"{n}.mixed" for n in GEN_CLOSED} | set(NEW) | {"programs_compiled", "compiles_in_window"}
    assert pr34 <= set(names)
    assert set(names) - pr34 <= set(check_appended(manifest.doc))
    for name in names:
        assert hasattr(plugin("readers", manifest.layer_metric(name)["reader"]), "read")


#: what ``BENCHMARK.json`` held once PR 34 was accepted, in its order: what
#: a later PR adds stands after these
ACCEPTED = {
    "configs": ["bert-base", "mistral-7b-l16", "mistral-7b-l12-x4", "trinity-mini-l5"],
    "workloads": ["bert-base_mlm-s512", "mistral-7b_gen-closed", "mistral-7b_pretrain-x4", CELL],
}
PR34_LAYERS = [f"{n}.mixed" for n in GEN_CLOSED] + list(NEW)


def check_appended(doc) -> list[str]:
    """The accepted entries come first and in their order, and anything
    after them is an addition: names stay unique, every configuration has a
    cell, PR 34's per-layer entries stand directly after ``serve_model_mfu``
    each reading the new cell alone, no earlier entry reads it, and the file
    stays small. Returns the per-layer names entered after PR 34's."""
    for key, names in ACCEPTED.items():
        assert [e["name"] for e in doc[key]][: len(names)] == names
    layers = [m["name"] for m in doc["per_layer"]]
    first_new = layers.index(PR34_LAYERS[0])
    end = first_new + len(PR34_LAYERS)
    assert layers[first_new - 1] == "serve_model_mfu"
    assert layers[first_new:end] == PR34_LAYERS
    for metric in doc["per_layer"][first_new:end]:
        assert metric["workloads"] == [CELL] and metric["moves"] == "output_tokens_per_s"
    for metric in doc["per_layer"][:first_new]:
        assert CELL not in metric.get("workloads", [])
    by_name = {m["name"]: m for m in doc["end_to_end"]}
    assert by_name["output_tokens_per_s"]["workloads"][:2] == ["mistral-7b_gen-closed", CELL]
    assert CELL not in by_name["tokens_per_s"]["workloads"]
    names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
    assert len(names) == len(set(names))
    assert {w["config"] for w in doc["workloads"]} == {c["name"] for c in doc["configs"]}
    assert sum(w["chips"] == 4 for w in doc["workloads"]) <= max(1, len(doc["workloads"]) // 4)
    assert len(json.dumps(doc, indent=1)) <= 64 * 1024
    return layers[end:]


def test_the_entries_stand_at_the_ends_of_their_lists(manifest):
    """The manifest as committed: PR 34's entries where it put them, and
    ``engine_epoch_drain_share`` for each serving cell appended after them."""
    later = check_appended(manifest.doc)
    assert later[:2] == ["engine_epoch_drain_share", "engine_epoch_drain_share.mixed"]
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_an_appended_configuration_cell_and_metric_need_no_edit(manifest, tmp_path):
    """A later PR's additions: one configuration, one cell on it, the cell
    added to its end-to-end metric's list and one per-layer entry with a
    ``workloads`` list, all appended — the checks above pass unchanged."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = json.loads(json.dumps(manifest.doc))
    cfg = dict(manifest.config("mistral-7b-l16"), num_hidden_layers=8)
    (root / "benchmark/configs/appended-probe.json").write_text(json.dumps(cfg))
    doc["configs"].append({"name": "appended-probe", "source": cfg["source"],
                           "file": "benchmark/configs/appended-probe.json",
                           "reduced": ["num_hidden_layers"], "why": "test"})
    doc["workloads"].append({"name": "appended-probe_gen-closed", "config": "appended-probe",
                             "traffic": "gen-closed", "chips": 1, "why": "test"})
    (e2e,) = [m for m in doc["end_to_end"] if m["name"] == "output_tokens_per_s"]
    e2e["workloads"].append("appended-probe_gen-closed")
    doc["per_layer"].append({"name": "serve_model_mfu.appended_probe", "unit": "%", "better": "higher",
                             "source": "program_counter", "layer": "model",
                             "moves": "output_tokens_per_s",
                             "workloads": ["appended-probe_gen-closed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))

    scratch = Manifest(root)
    assert check_appended(scratch.doc)[-1] == "serve_model_mfu.appended_probe"
    check_trinity_layers(scratch)
    assert [m["name"] for m in scratch.metrics_of("appended-probe_gen-closed", "per_layer")][-1] == (
        "serve_model_mfu.appended_probe")


def test_the_parameter_count_is_the_one_the_file_states(manifest):
    cfg = manifest.config("trinity-mini-l5")
    model, pc = afmoe.serve_model(cfg)
    abstract = afmoe.abstract_params(model)
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    stated = cfg["parameters"]
    assert count(abstract) == stated["total"] == 4_241_534_720
    assert stated["bytes_bf16"] == 2 * stated["total"]
    assert count(abstract["layers_0"]) == stated["dense_layer"]
    assert count(abstract["layers_4"]) == stated["expert_layer"]
    assert count(abstract["layers_1"]["attn"]) == stated["attention_per_layer"]
    # the leaves weights.py scales by name: stacks, router and gate are
    # `kernel`, the selection bias `bias`, every norm `scale`
    leaves = {
        "/".join(str(getattr(k, "key", k)) for k in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(abstract["layers_1"])[0]
    }
    assert {"experts/gate_proj/kernel", "experts/up_proj/kernel", "experts/down_proj/kernel",
            "experts/router/kernel", "experts/router/bias", "experts/shared/up_proj/kernel",
            "attn/gate_proj/kernel", "attn/q_norm/scale", "ln1_post/scale", "ln2_post/scale"} <= leaves
    assert pc.head_dim == 128 != pc.d_model // pc.n_heads
    assert [k.window for k in pc.kinds] == [2048] * 4 + [None]
    assert [k.rope for k in pc.kinds] == [True] * 4 + [False]
    assert [k.ffn for k in pc.kinds] == ["dense"] + ["moe"] * 4


def test_serve_context_equals_a_hand_count(manifest):
    """Active parameters, not held ones, and attention by layer kind, at a
    mix of one prompt length and one output length: 3,000 + 101 tokens."""
    cfg = manifest.config("trinity-mini-l5")
    one = lambda n: {"dist": "lognormal", "median": n, "sigma": 0.0, "min": n, "max": n}
    mix = {"prompt_tokens": one(3000), "output_tokens": one(101)}
    got = afmoe.serve_context(cfg, mix, cfg["serve"])
    h, d = 2048, 128
    attn = 3 * h * 32 * d + 2 * h * 4 * d                   # q, gate, o; k, v
    dense = 3 * h * 6144
    expert_layer = h * 128 + (8 + 1) * 3 * h * 1024         # router, 8 routed + shared
    body = 5 * attn + dense + 4 * expert_layer
    assert body == 401_604_608
    tokens = 3000 + 101 - 1                                 # the last token is never fed back
    heads = 101                                             # the prompt's last position + 100 decode steps
    full = tokens * (tokens + 1) // 2
    sliding = 2048 * 2049 // 2 + (tokens - 2048) * 2048
    flops = 2.0 * (tokens * body + heads * h * 200192) + 4.0 * 32 * d * (4 * sliding + full)
    assert got["forward_flops_per_token"] == pytest.approx(flops / tokens, rel=1e-12)
    assert got["mean_context_tokens"] == pytest.approx(full / tokens)
    assert got["mean_window_context_tokens"] == pytest.approx(sliding / tokens)
    assert got["moe_flops_per_assignment"] == 2.0 * 3 * 2048 * 1024
    assert got["moe_bytes_per_expert"] == 3 * 2048 * 1024 * 2 == 12_582_912
    assert (got["moe_experts"], got["moe_top_k"]) == (128.0, 8.0)


@pytest.fixture(scope="module")
def tiny(manifest):
    cfg = tiny_config(manifest)
    model, _ = afmoe.serve_model(cfg)
    params = seeded_params(afmoe.abstract_params(model), 11, jnp.float32)
    tokens = np.random.default_rng(0).integers(2, cfg["vocab_size"], size=40).astype(np.int32)
    return cfg, params, tokens


def test_the_reference_agrees_with_itself_in_blocks_and_whole(tiny):
    """Queries a block at a time against all at once, and each expert's
    product on the positions routed to it against every expert on every
    position, masked; a share of the experts adds up with the others."""
    cfg, params, tokens = tiny
    rows = np.arange(len(tokens))
    whole = np.asarray(reference.logits_at(params, tokens, rows, cfg, q_block=64, routed=False))
    blocks = np.asarray(reference.logits_at(params, tokens, rows, cfg, q_block=7, routed=True))
    assert whole.shape == (40, cfg["vocab_size"]) and np.isfinite(whole).all()
    np.testing.assert_allclose(blocks, whole, atol=2e-5 * whole.std())
    assert reference.layer_kinds(cfg) == [("sliding_attention", True)] + [
        ("sliding_attention", False)] * 3 + [("full_attention", False)]


def test_the_reference_imports_nothing_from_the_program():
    source = (ROOT / "benchmark" / "reference" / "afmoe.py").read_text()
    assert "import kubeflow_tpu" not in source and "from kubeflow_tpu" not in source
    assert 'default_matmul_precision("highest")' in source


# -- the new readers on a recorded counter set ---------------------------- #

US = 1e-6
SHAPES = {
    "context.moe_flops_per_assignment": 2.0 * 3 * 2048 * 1024,
    "context.moe_bytes_per_assignment": 2.0 * (3 * 2048 + 2 * 1024),
    "context.moe_bytes_per_expert": 12_582_912.0, "context.moe_experts": 128.0,
    "context.moe_top_k": 8.0, "context.max_batch": 64.0,
    "context.peak_flops_per_chip": 197e12, "context.peak_hbm_bytes_per_s": 819e9,
}


def recorded(kernel_us, *, layer_steps, assignments, touched, load_max=0.0, held=0.0, dead=0.0):
    """Evidence of a window in which ``layer_steps`` decode layer-steps ran,
    and a trace of two of them: six decode calls of ``kernel_us`` each (the
    down product under its other name) and one prefill piece's call."""
    call = "%moe_gmm_m512_k{k}_n{n}_t128x{k}x{n}.{i} = bf16[512,{n}] custom-call(%a, %b)"
    ops, at = [], 10.0
    for i in range(6):
        k, n = (1024, 2048) if i % 3 == 2 else (2048, 1024)
        text = call.format(k=k, n=n, i=i)
        ops.append(tr.Event(tr.short_name(text), at * US, (at + kernel_us) * US, text))
        at += kernel_us + 5
    # a fusion that names the call among its operands, and a piece's call
    text = "%fusion.3 = bf16[512,1024] fusion(%moe_gmm_m512_k2048_n1024_t128x2048x1024.0)"
    ops.append(tr.Event("fusion.3", at * US, (at + 50) * US, text))
    text = "%moe_gmm_m8192_k2048_n1024_t128x2048x1024.9 = bf16[8192,1024] custom-call(%a, %b)"
    ops.append(tr.Event(tr.short_name(text), (at + 60) * US, (at + 1060) * US, text))
    reduction = tr.reduce(tr.Trace({0: ops}, {0: []}, []))
    ev = Evidence(cell={"name": CELL}, trace=reduction)
    ev.numbers.update(SHAPES, **{
        "engine.moe_layer_steps_decode": layer_steps, "engine.moe_assignments_decode": assignments,
        "engine.moe_experts_touched_decode": touched, "engine.moe_load_max_decode": load_max,
        "engine.kv_pages_held": held, "engine.kv_pages_dead_window": dead,
    })
    return ev


def least_us(assignments, touched):
    flops = assignments * SHAPES["context.moe_flops_per_assignment"]
    nbytes = touched * 12_582_912 + assignments * SHAPES["context.moe_bytes_per_assignment"]
    return 1e6 * max(flops / 197e12, nbytes / 819e9)


def test_the_grouped_products_roofline_counts_the_work_that_was_there(manifest):
    spec = manifest.layer_metric("moe_gmm_roofline")
    # full rows: 512 assignments a layer-step touch 125 experts; the three
    # calls of a layer-step take 3 x 900 us
    full = recorded(900.0, layer_steps=1000.0, assignments=512_000.0, touched=125_000.0)
    assert least_us(512, 125) == pytest.approx(125 * 12_582_912 / 819e9 * 1e6, rel=0.01)
    assert moe_roofline.read(spec, full) == pytest.approx(100 * least_us(512, 125) / 2700.0)
    assert full.notes["moe_gmm_roofline_bound"] == "memory"
    # half the rows idle: 256 assignments touch 111 experts, and a kernel
    # that took exactly the least time for that reads 100 %, not more —
    # where a count at nominal occupancy (512, 128) would read 115 %
    ideal = least_us(256, 111)
    half = recorded(ideal / 3, layer_steps=1000.0, assignments=256_000.0, touched=111_000.0)
    assert moe_roofline.read(spec, half) == pytest.approx(100.0)
    assert 100 * least_us(512, 128) / ideal > 105
    # a program without the counters, a trace without the kernel: nothing
    none = recorded(900.0, layer_steps=0.0, assignments=0.0, touched=0.0)
    assert moe_roofline.read(spec, none) is None
    del full.numbers["engine.moe_experts_touched_decode"]
    assert moe_roofline.read(spec, full) is None
    assert moe_roofline.read(spec, Evidence(cell={"name": CELL})) is None
    rows96 = recorded(900.0, layer_steps=10.0, assignments=5120.0, touched=1250.0)
    rows96.numbers["context.max_batch"] = 96.0    # its decode call would be m768
    assert moe_roofline.read(spec, rows96) is None


def test_the_counter_metrics_and_the_time_share(manifest):
    ev = recorded(900.0, layer_steps=1000.0, assignments=512_000.0, touched=125_000.0,
                  load_max=11_000.0, held=40_000.0, dead=9_000.0)
    got = collect_metrics(manifest, ev, traced=True)
    assert got["moe_experts_touched_share"]["value"] == pytest.approx(100 * 125 / 128)
    # the fullest expert's 11 assignments a layer-step over the mean's 4
    assert got["moe_load_max_over_mean"]["value"] == pytest.approx(11 / 4)
    assert got["kv_window_dead_share"]["value"] == pytest.approx(22.5)
    # busy: 6 x 900 + 50 + 1000 us; the grouped product's share leaves the
    # fusion that only names it out
    busy = 6 * 900 + 50 + 1000
    assert got["moe_gmm_time_share"]["value"] == pytest.approx(100 * (busy - 50) / busy)
    assert got["moe_gmm_roofline"]["value"] == pytest.approx(100 * least_us(512, 125) / 2700.0)
    assert got["moe_gmm_roofline"]["value"] <= 100


# -- the cell end to end at a tiny size ----------------------------------- #

def test_the_cell_runs_end_to_end_at_a_tiny_size(manifest):
    cfg = tiny_config(manifest)
    cfg["serve"] = dict(cfg["serve"], max_batch=4, max_seq=160, page_size=16,
                        prefill_chunk=32, kv_pool_tokens=640, max_new_tokens=32)
    mix = dict(
        manifest.traffic("mixed-closed"), clients=6, ramp_s=0.5, max_requests_per_s=400,
        prompt_tokens={"dist": "lognormal", "median": 24, "sigma": 0.8, "min": 8, "max": 100},
        output_tokens={"dist": "lognormal", "median": 16, "sigma": 0.5, "min": 8, "max": 32},
        # a few seconds on a CPU finish too few tokens for a 99th percentile
        check={"requests": 6, "regret_max": 0.01, "regret_mean": 0.001,
               "measured": {"regret_max": 0.001, "regret_mean": 0.0001},
               "seeds": [3, 5, 7, 11, 13]},
    )
    ctx = RunContext(
        cell=manifest.cell(CELL), config=cfg, traffic=mix, seed=3, seconds=4.0, trace=False,
        device=FAKE_DEVICE, t_process=time.perf_counter(), profile_device=False,
    )
    ev = plugin("runners", mix["runner"]).run(ctx)
    assert ev.correct, ev.notes["check"]
    assert ev.failed == 0 and ev.attempted > 10
    # float32 on both sides: the engine's token is the reference's choice,
    # through prefill in pieces and decode through the pool, past the window
    assert ev.check["regret_max"] <= 1e-3 and max(ev.notes["check"]["lengths"]) > 8 * 4
    n = ev.numbers
    assert n["xla.compiles_in_window"] == 0
    # every live decode row is 4 assignments in each of 4 expert layers
    assert n["engine.moe_assignments_decode"] == pytest.approx(
        16 * (n["client.output_tokens"] - n["client.first_tokens"]), rel=0.1)
    # (a prompt's pieces are counted with its last one, so the window's
    # edges cut the two counters a few requests apart)
    assert n["engine.moe_assignments_prefill"] == pytest.approx(16 * n["engine.prefill_tokens"], rel=0.1)
    assert 0 < n["engine.moe_experts_touched_decode"] <= 16 * n["engine.moe_layer_steps_decode"]
    assert n["engine.moe_layer_steps_decode"] <= 4 * 8 * n["engine.chunks"]
    assert 0 < n["engine.kv_pages_dead_window"] < n["engine.kv_pages_held"]
    assert n["context.forward_flops_per_token"] == afmoe.serve_context(cfg, mix, None)["forward_flops_per_token"]
    got = collect_metrics(manifest, ev, traced=False)
    assert set(got) == {"output_tokens_per_s", "setup_s"}
    layers = collect_metrics(manifest, ev, traced=True)
    # no device trace here: the trace readers found nothing; the counters'
    # metrics are all there
    assert {"moe_experts_touched_share", "moe_load_max_over_mean", "kv_window_dead_share",
            "serve_model_mfu.mixed", "engine_batch_occupancy.mixed", "hbm_peak_gib.mixed",
            "engine_prefill_pad_share.mixed", "closed_ttft_ms_p50.mixed"} <= set(layers)
    assert 0 < layers["moe_experts_touched_share"]["value"] <= 100
    assert layers["moe_load_max_over_mean"]["value"] >= 1
    assert 0 < layers["kv_window_dead_share"]["value"] < 100
    assert 0 < layers["serve_model_mfu.mixed"]["value"] <= 100
    line = result_line(manifest, ev, FAKE_DEVICE, traced=False)
    assert list(line)[-1] == "check" and json.dumps(line)
