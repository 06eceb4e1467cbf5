"""``BENCHMARK.json`` against its contract, and the promise that a later PR
adds a cell by adding files and one entry, editing nothing."""

import json
import re
import shutil

import pytest

from benchmark.manifest import ROOT, Manifest, plugin
from benchmark.run import collect_metrics

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden_size|intermediate_size|head_dim|_dim$|_rank$|num_experts_per_tok|expansion)")


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


def test_exactly_the_contracts_keys(manifest):
    assert set(manifest.doc) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert manifest.doc["command"] == ["python3", "-m", "benchmark.run"]
    assert 1 <= manifest.doc["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_lines(manifest):
    doc = manifest.doc
    names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
    assert len(names) == len(set(names))
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in doc[group]:
            assert NAME.match(entry["name"]), entry["name"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in doc["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in doc["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for entry in doc["workloads"] + doc["configs"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


def test_every_config_has_a_cell_and_a_file_of_its_own(manifest):
    doc = manifest.doc
    used = {w["config"] for w in doc["workloads"]}
    assert used == {c["name"] for c in doc["configs"]}
    files = [c["file"] for c in doc["configs"]]
    assert len(files) == len(set(files))
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in doc["configs"]:
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
        body = manifest.config(c["name"])
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] and "assumed" in body
        assert not any(WIDTH.search(k) for k in c["reduced"]), "a width may never be reduced"


def test_published_widths_are_not_cut(manifest):
    m = manifest.config("mistral-7b-l16")
    assert (m["hidden_size"], m["intermediate_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["vocab_size"], m["sliding_window"]) == (
        4096, 14336, 32, 8, 32000, 4096)
    x4 = manifest.config("mistral-7b-l12-x4")
    assert {k: x4[k] for k in ("hidden_size", "intermediate_size", "sliding_window")} == {
        k: m[k] for k in ("hidden_size", "intermediate_size", "sliding_window")}
    b = manifest.config("bert-base")
    assert (b["hidden_size"], b["num_hidden_layers"], b["intermediate_size"],
            b["vocab_size"]) == (768, 12, 3072, 30522)


def test_at_most_a_quarter_of_the_cells_take_four_chips(manifest):
    cells = manifest.doc["workloads"]
    assert all(w["chips"] in (1, 4) for w in cells)
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


def test_every_cell_reports_setup_another_metric_and_a_layer(manifest):
    for w in manifest.doc["workloads"]:
        e2e = {m["name"] for m in manifest.metrics_of(w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        layers = manifest.metrics_of(w["name"], "per_layer")
        assert layers, w["name"]
        for m in layers:
            # a per-layer metric moves an end-to-end metric of the same cell
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_every_named_file_exists_and_names_a_plugin(manifest):
    cells = {w["name"] for w in manifest.doc["workloads"]}
    for m in manifest.doc["end_to_end"] + manifest.doc["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
    for w in manifest.doc["workloads"]:
        assert hasattr(plugin("runners", manifest.traffic(w["traffic"])["runner"]), "run")
        assert hasattr(plugin("families", manifest.config(w["config"])["family"]), "program_config")
    for m in manifest.doc["per_layer"]:
        assert hasattr(plugin("readers", manifest.layer_metric(m["name"])["reader"]), "read")
    for m in manifest.doc["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]


def test_every_serving_mix_states_the_limits_of_its_check(manifest):
    """A traffic file whose runner serves a model carries a valid ``check``
    and the reason for it, whether a cell runs it yet or not; a training
    mix carries its own two limits."""
    from benchmark import check

    served = 0
    for path in sorted((ROOT / "benchmark" / "traffic").glob("*.json")):
        mix = json.loads(path.read_text())
        assert len(mix["check_why"]) > 40, path.name
        if mix["runner"].startswith("serve_"):
            served += 1
            assert check.validate(mix) == mix["check"], path.name
        else:
            assert set(mix["check"]) == {"nll_err_max", "nll_err_mean"}, path.name
    assert served >= 2
    # the accepted serving cell: the same two statistics of the same three
    # requests, its limits set anew from this PR's readings (PERF.md section 4)
    block = manifest.traffic("gen-closed")["check"]
    assert (block["regret_max"], block["regret_mean"], block["requests"]) == (0.2, 0.004, 3)
    assert "regret_p99" not in block and "argmax_share_min" not in block
    assert manifest.traffic("docqa-open")["check"] == block


def test_serve_model_mfu_is_the_serving_cells_share_of_the_whole_step(manifest):
    (entry,) = [m for m in manifest.doc["per_layer"] if m["name"] == "serve_model_mfu"]
    assert entry == {"name": "serve_model_mfu", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "model",
                     "moves": "output_tokens_per_s", "workloads": ["mistral-7b_gen-closed"]}
    spec = manifest.layer_metric("serve_model_mfu")
    assert spec["reader"] == "ratio" and "context.forward_flops_per_token" in spec["num"]
    # every cell that serves a model has a share of the whole step beside it
    for w in manifest.doc["workloads"]:
        runner = manifest.traffic(w["traffic"])["runner"]
        names = {m["name"].split(".")[0] for m in manifest.metrics_of(w["name"], "per_layer")}
        assert ("serve_model_mfu" if runner.startswith("serve_") else "model_mfu") in names, w["name"]


def test_a_pr_shaped_addition_needs_no_edit(tmp_path):
    """One config file, one traffic file, one layer-metric file and one
    entry each: the harness finds them by name."""
    from benchmark.evidence import Evidence

    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*") if p.is_file()}
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())

    tiny = dict(Manifest().config("mistral-7b-l16"), num_hidden_layers=2)
    (root / "benchmark/configs/new-model.json").write_text(json.dumps(tiny))
    (root / "benchmark/traffic/chat-burst.json").write_text(json.dumps({
        "runner": "serve_open", "ramp_s": 1.0,
        "arrivals": {"process": "poisson", "rate_rps": 2.0},
        "prompt_tokens": {"dist": "lognormal", "median": 400, "sigma": 0.8, "min": 32, "max": 2048},
        "output_tokens": {"dist": "lognormal", "median": 200, "sigma": 0.8, "min": 16, "max": 1024}}))
    (root / "benchmark/layer_metrics/engine_prefill_ms_p90.json").write_text(json.dumps(
        {"reader": "span", "span": "prefill", "reduce": "p90"}))
    doc["configs"].append({"name": "new-model", "source": tiny["source"],
                           "file": "benchmark/configs/new-model.json",
                           "reduced": ["num_hidden_layers"], "why": "test"})
    doc["workloads"].append({"name": "new-model_chat-burst", "config": "new-model",
                             "traffic": "chat-burst", "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "engine_prefill_ms_p90", "unit": "ms", "better": "lower",
                             "source": "program_span", "layer": "engine", "moves": "setup_s",
                             "workloads": ["new-model_chat-burst"]})
    # a reading that exists, moving another metric: an entry, no file
    doc["per_layer"].append({"name": "engine_decode_step_ms.burst", "unit": "ms", "better": "lower",
                             "source": "program_span", "layer": "engine", "moves": "setup_s",
                             "workloads": ["new-model_chat-burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    m = Manifest(root)
    cell = m.cell("new-model_chat-burst")
    assert m.config(cell["config"])["num_hidden_layers"] == 2
    mix = m.traffic(cell["traffic"])
    assert plugin("runners", mix["runner"]).run
    ev = Evidence(cell=cell, traces=[{"trace_id": "t", "spans": [
        {"name": "prefill", "start_ms": 1.0, "end_ms": 4.5, "status": "ok", "attrs": {}},
        {"name": "decode.chunk", "start_ms": 5.0, "end_ms": 21.0, "status": "ok", "attrs": {}}]}])
    ev.numbers.update({"xla.programs": 7.0, "xla.compiles_in_window": 0.0,
                       "context.chunk_steps": 8.0})
    got = collect_metrics(m, ev, traced=True)
    assert got["engine_prefill_ms_p90"] == {"value": 3.5, "unit": "ms"}
    assert got["engine_decode_step_ms.burst"] == {"value": 2.0, "unit": "ms"}
    assert got["programs_compiled"]["value"] == 7.0
    # metrics of other cells, and readers that found nothing, are left out
    assert "engine_decode_step_ms" not in got and "device_idle_share" not in got
    after = {p: p.read_bytes() for p in before}
    assert after == before, "an existing file was edited"


def test_missing_files_are_named(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({"workloads": [], "configs": []}))
    m = Manifest(tmp_path)
    with pytest.raises(KeyError, match="no workload"):
        m.cell("nope")
    with pytest.raises(FileNotFoundError, match="named by BENCHMARK.json"):
        m.traffic("nope")
    with pytest.raises(ValueError):
        plugin("runners", "../evil")
