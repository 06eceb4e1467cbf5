"""Device time by part of the program (``benchmark/scope_reduce.py``, the
reader ``trace_scope`` and the scopes the programs carry): the parser on a
small ``XSpace`` written with the protobuf classes, every number counted by
hand; the reader with nothing to read; and the programs of every cell,
lowered at a tiny size, named by a part of the metric files' vocabulary
down to every product, kernel, gather, scatter and draw."""

import dataclasses
import json
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import scope_reduce as sr
from benchmark.evidence import Evidence
from benchmark.manifest import ROOT, Manifest, plugin
from benchmark.readers import trace_scope

US = 1e-6
CHUNK = "jit(_chunk_paged_impl)/while/body/closed_call"
#: (tf_op, how the trace gives it) of each operation below; ``None``: no stat
OPS = {
    1: ("%while.1 = (s32[]) while((s32[]) %t), body=%b", "jit(_chunk_paged_impl)/while", "str"),
    2: ("%fusion.2 = bf16[8] fusion(bf16[8] %p)", f"{CHUNK}/TransformerLM/layers_0/attn/q_proj/dot_general", "str"),
    3: ("%fusion.3 = s32[8] fusion(f32[8] %p)", f"{CHUNK}/sample/argmax", "ref"),
    4: ("%copy.4 = bf16[8] copy(bf16[8] %p)", "params['layers_0']['attn']['q_proj']['kernel']", "str"),
    5: ("%fusion.5 = f32[8] fusion(f32[8] %p)", "jit(step)/transpose(jvp(loss))/mul", "str"),
    6: ("%fusion.6 = f32[8] fusion(f32[8] %p)", "jit(step)/optimizer/add", "str"),
    7: ("%copy-done.7 = f32[8] copy-done(f32[8] %p)", None, None),
    8: ("%fusion.8 = f32[8] fusion(f32[8] %p)", "jit(_chunk_paged_impl)/while/body/closed_call/add", "str"),
}
#: (metadata id, start us, duration us) on the operation line
EVENTS = [
    (2, 20, 60),     # before the window opens at 50: 30 us inside
    (1, 100, 400),   # the loop: 400 us less what it contains
    (2, 120, 100),
    (3, 250, 50),
    (4, 300, 40),
    (8, 360, 20),
    (2, 400, 60),
    (5, 610, 90),
    (6, 700, 150),
    (7, 850, 100),   # past the window's end at 900: 50 us inside
]
MODULES = [("jit__chunk_paged_impl(111)", 10, 560), ("jit_step(222)", 600, 400)]
PARTS = {
    "attn": [r"(^|[/(])(attn)([/):]|$)|\['(attn)'\]"],
    "block": [r"(^|[/(])(layers_[0-9]+)([/):]|$)|\['(layers_[0-9]+)'\]"],
    "sample": [r"(^|[/(])(sample)([/):]|$)"],
    "loss": [r"(^|[/(])(loss)([/):]|$)"],
    "optimizer": [r"(^|[/(])(optimizer)([/):]|$)"],
}


def write_space(path, chips=1):
    pb = sr.xplane_pb2()
    space = pb.XSpace()
    dev = space.planes.add(id=1, name="/device:TPU:0")
    dev.stat_metadata[1].id, dev.stat_metadata[1].name = 1, "tf_op"
    dev.stat_metadata[2].id, dev.stat_metadata[2].name = 2, "hlo_category"
    for k, (text, tf_op, how) in OPS.items():
        md = dev.event_metadata[k]
        md.id, md.name = k, text
        md.stats.add(metadata_id=2, str_value="convolution")
        if how == "str":
            md.stats.add(metadata_id=1, str_value=tf_op)
        elif how == "ref":
            # the trace may give the value as a reference to a stat's name
            dev.stat_metadata[100 + k].id, dev.stat_metadata[100 + k].name = 100 + k, tf_op
            md.stats.add(metadata_id=1, ref_value=100 + k)
    for k, (name, _, _) in enumerate(MODULES, start=50):
        dev.event_metadata[k].id, dev.event_metadata[k].name = k, name
    ops = dev.lines.add(id=1, name="XLA Ops", timestamp_ns=1000)
    for k, start, dur in EVENTS:
        ops.events.add(metadata_id=k, offset_ps=start * 10**6, duration_ps=dur * 10**6)
    mods = dev.lines.add(id=2, name="XLA Modules", timestamp_ns=1000)
    for k, (_, start, dur) in enumerate(MODULES, start=50):
        mods.events.add(metadata_id=k, offset_ps=start * 10**6, duration_ps=dur * 10**6)
    for chip in range(1, chips):
        # every chip of a sharded step runs the same timeline
        space.planes.add().CopyFrom(dev)
        space.planes[-1].id, space.planes[-1].name = 1 + chip, f"/device:TPU:{chip}"
    host = space.planes.add(id=9, name="/host:CPU")
    host.event_metadata[1].id, host.event_metadata[1].name = 1, "bench.trace_window"
    host.event_metadata[2].id, host.event_metadata[2].name = 2, "bench.client_send"
    line = host.lines.add(id=1, name="python3", timestamp_ns=1000)
    line.events.add(metadata_id=1, offset_ps=50 * 10**6, duration_ps=850 * 10**6)
    line.events.add(metadata_id=2, offset_ps=60 * 10**6, duration_ps=10 * 10**6)
    path.write_bytes(space.SerializeToString())
    return path


@pytest.fixture(scope="module")
def reduction(tmp_path_factory):
    return sr.reduce(sr.load(write_space(tmp_path_factory.mktemp("xspace") / "t.xplane.pb")))


def test_the_window_is_the_benchmarks_annotation(reduction):
    assert reduction.window == pytest.approx((1000e-9 + 50 * US, 1000e-9 + 900 * US))
    assert reduction.chips == 1 and reduction.events == len(EVENTS)
    assert reduction.mismatched == 0


def test_self_times_by_program_and_part(reduction):
    parts = reduction.by_part(PARTS)
    chunk, step = "jit__chunk_paged_impl", "jit_step"
    assert parts == pytest.approx({
        # 30 (cut by the window's start) + 100 + 60 us of attention; the
        # weight's copy named by its argument's path is attention's too
        (chunk, "attn"): (30 + 100 + 60 + 40) * US,
        (chunk, "sample"): 50 * US,
        # the loop's own 400 - (100 + 50 + 40 + 20 + 60) and the step's add
        (chunk, None): (130 + 20) * US,
        # the loss's backward keeps the scope inside the transformation
        (step, "loss"): 90 * US,
        (step, "optimizer"): 150 * US,
        # no tf_op: the module line alone says whose it is; cut at 900
        (step, None): 50 * US,
    })
    # a program's parts and its unscoped rest add up to its self time
    for program, busy in ((chunk, 430), (step, 290)):
        assert sum(s for (p, _), s in parts.items() if p == program) == pytest.approx(busy * US)
        assert sum(s for (p, _, _), s in reduction.seconds.items() if p == program) == (
            pytest.approx(busy * US))
    assert reduction.busy_s == pytest.approx((190 + 40 + 50 + 150 + 90 + 150 + 50) * US)
    assert reduction.unscoped(PARTS) == [
        ["jit(_chunk_paged_impl)", pytest.approx(130 * US)],
        ["<no tf_op> copy-done", pytest.approx(50 * US)],
        ["jit(_chunk_paged_impl)/while/body/closed_call", pytest.approx(20 * US)],
    ]


def test_chips_are_summed_and_shares_kept(tmp_path, reduction):
    four = sr.reduce(sr.load(write_space(tmp_path / "t.xplane.pb", chips=4)))
    assert four.chips == 4 and four.events == 4 * len(EVENTS)
    assert four.by_part(PARTS) == pytest.approx(
        {k: 4 * v for k, v in reduction.by_part(PARTS).items()})
    described = sr.describe(four, PARTS)
    assert described["busy_s"] == pytest.approx(720 * US)
    assert described["unscoped_percent_of_busy"] == pytest.approx(100 * 200 / 720)


def test_the_innermost_part_wins(reduction):
    compiled = sr._compile(PARTS)
    assert sr.part_of(f"{CHUNK}/TransformerLM/layers_0/attn/q_proj", compiled) == "attn"
    assert sr.part_of(f"{CHUNK}/TransformerLM/layers_0/add", compiled) == "block"
    assert sr.part_of("params['layers_0']['attn']['q_proj']['kernel']", compiled) == "attn"
    assert sr.part_of(f"{CHUNK}/sample_logits/reduce", compiled) is None
    assert sr.program_of_tf_op("jit(step)/optimizer/add") == "jit_step"
    assert sr.program_of_tf_op("params['embed']") is None
    assert sr.program_of_module("jit__chunk_paged_impl(123)") == "jit__chunk_paged_impl"


def test_a_disagreeing_program_is_counted(tmp_path):
    trace = sr.load(write_space(tmp_path / "t.xplane.pb"))
    trace.modules[0][0] = dataclasses.replace(trace.modules[0][0], name="jit_other(1)")
    r = sr.reduce(trace)
    # the six chunk operations whose tf_op names another program than the
    # module event around them; the module line's name is the one kept
    assert r.mismatched == 6
    split = r.by_part(PARTS)
    assert split[("jit_other", "attn")] == pytest.approx(230 * US)
    assert not any(p == "jit__chunk_paged_impl" for p, _ in split)


def test_the_reader_reads_the_metric_files(reduction, monkeypatch):
    ev = Evidence(cell={"name": "x"}, trace=object())
    monkeypatch.setattr(trace_scope, "reduction", lambda ev: reduction)
    attn = {"what": "program_share", "program": "^jit__chunk", "parts": {"attn": PARTS["attn"]}}
    assert trace_scope.read(attn, ev) == pytest.approx(100 * 230 / 430)
    everything = {"what": "unscoped_share", "parts": PARTS}
    assert trace_scope.read(everything, ev) == pytest.approx(100 * 200 / 720)
    assert trace_scope.read(dict(attn, program="^jit_none$"), ev) is None


def test_the_reader_reads_nothing_without_a_trace(tmp_path, monkeypatch):
    spec = Manifest(ROOT).layer_metric("device_unscoped_share")
    assert trace_scope.read(spec, Evidence(cell={"name": "x"})) is None
    # a traced run whose profile left no file
    monkeypatch.setattr(trace_scope.harness, "OUT_DIR", tmp_path)
    assert trace_scope.read(spec, Evidence(cell={"name": "x"}, trace=object())) is None


def test_the_reader_parses_the_trace_once(tmp_path, monkeypatch):
    monkeypatch.setattr(trace_scope.harness, "OUT_DIR", tmp_path)
    run = tmp_path / "trace" / "cell" / "plugins" / "profile" / "1"
    run.mkdir(parents=True)
    write_space(run / "h.xplane.pb")
    calls = []
    real = sr.load
    monkeypatch.setattr(trace_scope.scope_reduce, "load", lambda p: calls.append(p) or real(p))
    ev = Evidence(cell={"name": "cell"}, trace=object())
    manifest = Manifest(ROOT)
    for name in ("device_unscoped_share", "decode_attn_share", "decode_head_share"):
        assert trace_scope.read(manifest.layer_metric(name), ev) is not None
    assert len(calls) == 1


def test_the_command_line_describes_a_trace(tmp_path, capsys):
    assert sr.main([str(write_space(tmp_path / "t.xplane.pb")), "--program", "chunk"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert list(out["programs"]) == ["jit__chunk_paged_impl"]
    parts = out["programs"]["jit__chunk_paged_impl"]["parts"]
    assert parts["attn"]["seconds"] == pytest.approx(230 * US)
    assert sum(p["percent"] for p in parts.values()) == pytest.approx(100)
    assert "tensorflow" not in sys.modules


# -- the programs of every cell, lowered at a tiny size ------------------- #

#: the instructions that do a program's work: products, kernels, the pool's
#: and the embedding's reads and writes, random draws
HEAVY = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? (dot|convolution|custom-call|gather|scatter|rng"
    r"|rng-bit-generator)\("
)
TINY_SERVE = {
    "mistral-7b-l16": dict(
        hidden_size=256, num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=1,
        intermediate_size=256, vocab_size=256, sliding_window=64,
        activation_dtype="float32", weight_dtype="float32"),
    "trinity-mini-l5": dict(
        hidden_size=256, num_attention_heads=2, num_key_value_heads=1, head_dim=128,
        sliding_window=64, intermediate_size=256, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=128, vocab_size=256, max_position_embeddings=512,
        experts_held={"first": 0, "count": 8},
        activation_dtype="float32", weight_dtype="float32"),
}


@pytest.fixture(scope="module")
def vocabulary():
    return sr._compile(sr.vocabulary())


def test_every_scope_the_programs_use_is_a_part_of_the_vocabulary(vocabulary):
    from kubeflow_tpu.core import parts

    for name in parts.PARTS:
        assert sr.part_of(f"jit(f)/{name}/add:", vocabulary) == name
        assert sr.part_of(f"jit(f)/transpose(jvp({name}))/mul", vocabulary) == name


def unnamed(text: str, compiled) -> list[str]:
    """The heavy instructions whose ``op_name`` falls under no part (an
    instruction the compiler made carries no ``op_name`` and is no one's)."""
    out = []
    for line in text.splitlines():
        if not HEAVY.match(line):
            continue
        m = re.search(r'op_name="((?:[^"\\]|\\.)*)"', line)
        if m and sr.part_of(m.group(1).replace("\\'", "'"), compiled) is None:
            out.append(line.strip()[:200])
    return out


def serve_programs(config: str) -> dict[str, str]:
    """The decode chunk, a prefill piece and the merge of a small engine on
    the cell's configuration, the kernels in interpret mode, compiled from
    the arguments of their first calls."""
    from kubeflow_tpu.models.transformer import TransformerLM
    from kubeflow_tpu.serve.engine import LMEngine

    from benchmark.weights import seeded_params

    cfg = dict(Manifest(ROOT).config(config), **TINY_SERVE[config])
    family = plugin("families", cfg["family"])
    pc = family.program_config(cfg, interpret_kernels=True)
    model = TransformerLM(pc)
    params = seeded_params(family.abstract_params(model), 3, jnp.float32)
    eng = LMEngine(model, pc, params, max_batch=2, max_seq=256, chunk_steps=2, page_size=16,
                   prefill_chunk=128, kv_pool_tokens=1024, eos_id=cfg["vocab_size"] + 1)
    jitted = {name: getattr(eng, name) for name in ("_chunk", "_suffix_prefill", "_merge")}
    seen = {}

    def recorder(name):
        def record(*args, **kw):
            seen.setdefault(name, (jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype), args), kw))
            return jitted[name](*args, **kw)
        return record

    for name in jitted:
        setattr(eng, name, recorder(name))
    eng.start()
    try:
        assert len(eng.submit(list(range(2, 40)), max_new_tokens=6)) == 6
    finally:
        eng.stop()
    return {
        name: jitted[name].lower(*args, **kw).compile().as_text()
        for name, (args, kw) in seen.items()
    }


def train_program(cell: str) -> str:
    """One train step of the cell's configuration at a tiny size, on a mesh
    of the cell's kind over this process's CPU devices."""
    import optax

    from kubeflow_tpu.core.mesh import MeshSpec
    from kubeflow_tpu.parallel.sharding import transformer_rules
    from kubeflow_tpu.train.loop import TrainConfig, Trainer

    manifest = Manifest(ROOT)
    cell = manifest.cell(cell)
    cfg = dict(manifest.config(cell["config"]), hidden_size=64, num_hidden_layers=2,
               num_attention_heads=4, intermediate_size=128, vocab_size=512,
               activation_dtype="float32")
    if cfg["family"] == "bert":
        cfg["max_position_embeddings"] = 64
    else:
        cfg.update(num_key_value_heads=2, sliding_window=16, weight_dtype="float32")
    train = dict(cfg["train"], program=dict(cfg["train"].get("program", {}), attn_impl="reference"))
    chips = jax.device_count()
    if "mesh" in train:
        train["mesh"] = {"data": chips // 4, "fsdp": 2, "model": 2}
    cfg["train"] = train
    mix = dict(manifest.traffic(cell["traffic"]), seq_len=32, global_batch=chips)
    setup = plugin("families", cfg["family"]).train_setup(cfg, mix, 0)
    trainer = Trainer(
        init_params=setup["init_params"], loss_fn=setup["loss_fn"], optimizer=optax.adamw(1e-4),
        config=TrainConfig(
            mesh=MeshSpec(**train["mesh"]) if "mesh" in train else MeshSpec.data_parallel(chips),
            global_batch=chips, steps=1, seed=0, handle_sigterm=False),
        param_spec_fn=transformer_rules() if "mesh" in train else None,
    )
    state = trainer.init_state()
    batch = trainer.global_batch_array(next(iter(setup["data"](0))))
    return trainer._build_step(state).lower(state, batch).compile().as_text()


@pytest.mark.parametrize("config", sorted(TINY_SERVE))
def test_every_serving_program_is_named_by_part(vocabulary, config):
    programs = serve_programs(config)
    assert set(programs) == {"_chunk", "_suffix_prefill", "_merge"}
    for name, text in programs.items():
        assert unnamed(text, vocabulary) == [], name
    manifest = Manifest(ROOT)
    for metric in ("decode_attn_share", "decode_head_share"):
        # each part a decode metric reads is there in the decode chunk
        for part, patterns in manifest.layer_metric(metric)["parts"].items():
            rx = re.compile(patterns[0])
            assert any(rx.search(n.replace("\\'", "'")) for n in re.findall(
                r'op_name="((?:[^"\\]|\\.)*)"', programs["_chunk"])), (metric, part)
    # the merge is the merge's alone
    names = set(re.findall(r'op_name="(jit[^"]*)"', programs["_merge"]))
    assert names and all("/merge/" in n for n in names)


@pytest.mark.parametrize("cell", ["bert-base_mlm-s512", "mistral-7b_pretrain-x4"])
def test_every_train_step_is_named_by_part(vocabulary, cell):
    text = train_program(cell)
    assert unnamed(text, vocabulary) == []
    names = re.findall(r'op_name="((?:[^"\\]|\\.)*)"', text)
    spec = Manifest(ROOT).layer_metric("train_head_loss_share")
    for part, patterns in spec["parts"].items():
        assert any(re.search(patterns[0], n) for n in names), part
    # the backward keeps the scopes: the loss's and the head's gradients
    assert any("transpose(jvp(loss))" in n for n in names)
    assert any(re.search(r"transpose\(jvp\(\w+\)\).*/unembed/", n) for n in names)
    assert any(re.match(r"jit\(step\)/optimizer/", n) for n in names)
