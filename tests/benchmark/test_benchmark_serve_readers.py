"""The serving engine's device-side and scheduler readings (PR 24): the
``trace_module`` and ``counter_share`` readers on a recorded trace of their
own (``data/serve_trace.textproto``: one chip, decode chunks and prefill
pieces on the module line, the scheduler's ``engine.*`` phases on a host
thread; every number below is counted by hand from that file)."""

from pathlib import Path

import pytest

from benchmark import trace_reduce as tr
from benchmark.evidence import Evidence
from benchmark.manifest import Manifest
from benchmark.readers import counter_share, trace_module
from benchmark.run import collect_metrics

US = 1e-6
TRACE = Path(__file__).parent / "data" / "serve_trace.textproto"
CELL = "mistral-7b_gen-closed"
NEW = (
    "engine_decode_device_ms", "engine_prefill_device_ms_p50", "engine_prefill_device_share",
    "engine_prefill_pad_share", "engine_sched_blocked_share", "engine_sched_host_ms_per_chunk",
)


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


@pytest.fixture(scope="module")
def reduction():
    from jax.profiler import ProfileData

    return tr.reduce(tr.load(ProfileData.from_text_proto(TRACE.read_text())))


def evidence(reduction, **numbers):
    ev = Evidence(cell={"name": CELL}, trace=reduction)
    ev.numbers.update(numbers)
    return ev


def test_module_durations_are_of_programs_wholly_inside_the_window(manifest, reduction):
    assert reduction.window == pytest.approx((100 * US, 1100 * US))
    ev = evidence(reduction, **{"context.chunk_steps": 8.0})
    # chunks of 160, 180 and 170 us inside (the first began before the window)
    decode = manifest.layer_metric("engine_decode_device_ms")
    assert trace_module.read(decode, ev) == pytest.approx(0.170 / 8)
    assert trace_module.read(dict(decode, reduce="max"), ev) == pytest.approx(0.180 / 8)
    # prefill pieces of 60, 40 and 50 us inside (the last outlasts the window)
    prefill = manifest.layer_metric("engine_prefill_device_ms_p50")
    assert trace_module.read(prefill, ev) == pytest.approx(0.050)
    # the number it divides by is not there: nothing, not a wrong number
    assert trace_module.read(decode, evidence(reduction)) is None


def test_window_share_cuts_programs_at_the_windows_edges(manifest, reduction):
    share = manifest.layer_metric("engine_prefill_device_share")
    # 60 + 40 + 50 us and the 20 us of the last piece that fall inside 1,000 us
    assert trace_module.read(share, evidence(reduction)) == pytest.approx(17.0)
    decode = dict(share, module=manifest.layer_metric("engine_decode_device_ms")["module"])
    # 150 us of the first chunk + 160 + 180 + 170
    assert trace_module.read(decode, evidence(reduction)) == pytest.approx(66.0)


def test_a_program_that_did_not_run_or_no_trace_reads_nothing(reduction):
    for what in ("duration_ms", "window_share"):
        spec = {"module": r"^jit__no_such_program\b", "what": what}
        assert trace_module.read(spec, evidence(reduction)) is None
        assert trace_module.read(spec, Evidence(cell={})) is None
    with pytest.raises(ValueError, match="unknown trace_module reading"):
        trace_module.read({"module": "x", "what": "p50"}, evidence(reduction))


def test_idle_gaps_take_the_schedulers_phases_as_their_cause(reduction):
    """No code reads the phases for this: the reduction labels a gap with
    the innermost host event open at its middle."""
    assert reduction.busy_s == pytest.approx(830 * US)
    gaps = dict(map(tuple, reduction.idle_gaps_by_host_activity()))
    assert gaps == pytest.approx({
        "engine.park": 90 * US,              # 990..1080
        "engine.prefill_dispatch": 30 * US,  # 250..260 and 680..700, inside admit
        "engine.carry_upload": 20 * US,      # 740..760
        "engine.admit": 10 * US,             # 320..330: after the wait, still admitting
        "engine.chunk_dispatch": 10 * US,    # 490..500
        "engine.drain_emit": 10 * US,        # 930..940
    })


def test_counter_share_adds_and_subtracts_before_it_divides(manifest):
    ev = Evidence(cell={"name": CELL})
    ev.numbers.update({
        "engine.prefill_tokens": 228.0 * 4, "engine.prefill_padded_tokens": 512.0 * 4,
        "engine.sched_loop_s": 10.0, "engine.sched_drain_wait_s": 7.0,
        "engine.sched_prefill_wait_s": 2.0, "engine.sched_park_s": 0.5, "engine.chunks": 50.0,
    })
    pad = manifest.layer_metric("engine_prefill_pad_share")
    assert counter_share.read(pad, ev) == pytest.approx(100 * (1 - 228 / 512))
    blocked = manifest.layer_metric("engine_sched_blocked_share")
    assert counter_share.read(blocked, ev) == pytest.approx(90.0)
    host = manifest.layer_metric("engine_sched_host_ms_per_chunk")
    assert counter_share.read(host, ev) == pytest.approx(1000 * 0.5 / 50)


def test_counter_share_reads_nothing_from_a_zero_denominator_or_a_missing_counter(manifest):
    pad = manifest.layer_metric("engine_prefill_pad_share")
    idle = Evidence(cell={"name": CELL})
    idle.numbers.update({"engine.prefill_tokens": 0.0, "engine.prefill_padded_tokens": 0.0})
    assert counter_share.read(pad, idle) is None
    # a program without the counter (the parent of PR 24)
    assert counter_share.read(pad, Evidence(cell={"name": CELL})) is None
    assert counter_share.read({"plus": ["a"], "over": []}, Evidence(cell={})) is None


def test_the_six_new_entries_read_beside_the_old_ones(manifest, reduction):
    entries = {m["name"]: m for m in manifest.doc["per_layer"]}
    # appended, in this order, after everything PR 22 had (PR 28 appended
    # ``serve_model_mfu`` after them)
    names = [m["name"] for m in manifest.doc["per_layer"]]
    first = names.index(NEW[0])
    assert first == 18 and tuple(names[first:first + 6]) == NEW
    for name in NEW:
        assert entries[name]["layer"] == "engine"
        assert entries[name]["moves"] == "output_tokens_per_s"
        assert entries[name]["workloads"] == [CELL]
        spec = manifest.layer_metric(name)
        want = "device_trace" if spec["reader"] == "trace_module" else "program_counter"
        assert entries[name]["source"] == want
    ev = evidence(reduction, **{
        "context.chunk_steps": 8.0, "xla.programs": 14.0, "xla.compiles_in_window": 0.0,
        "engine.prefill_tokens": 100.0, "engine.prefill_padded_tokens": 400.0,
        "engine.sched_loop_s": 4.0, "engine.sched_drain_wait_s": 3.0,
        "engine.sched_prefill_wait_s": 0.5, "engine.sched_park_s": 0.0, "engine.chunks": 20.0,
    })
    got = collect_metrics(manifest, ev, traced=True)
    assert set(NEW) <= set(got)
    assert got["engine_prefill_pad_share"] == {"value": pytest.approx(75.0), "unit": "%"}
    assert got["engine_sched_host_ms_per_chunk"] == {"value": pytest.approx(25.0), "unit": "ms"}
    assert {"device_idle_share.closed", "programs_compiled"} <= set(got)
    # the parent's program has the module line and none of the counters: the
    # trace's three read, the counters' three are left out, nothing raises
    parent = evidence(reduction, **{"context.chunk_steps": 8.0})
    got = collect_metrics(manifest, parent, traced=True)
    assert set(NEW) & set(got) == set(NEW[:3])


def test_the_epoch_drain_share_reads_the_two_counters_of_each_serving_cell(manifest):
    """``engine_epoch_drain_share``: the carry rebuilds (epochs) that found
    the pipeline empty, of all of them. Each serving cell reads it under its
    own entry; a program without the two counters reads nothing."""
    spec = manifest.layer_metric("engine_epoch_drain_share")
    assert spec == {"reader": "counter_share", "plus": ["engine.epoch_drains"],
                    "over": ["engine.epochs"], "scale": 100.0}
    assert manifest.layer_metric("engine_epoch_drain_share.mixed") == spec
    for cell, name in ((CELL, "engine_epoch_drain_share"),
                       ("trinity-mini_mixed-closed", "engine_epoch_drain_share.mixed")):
        (entry,) = [m for m in manifest.doc["per_layer"] if m["name"] == name]
        assert entry == {"name": name, "unit": "%", "better": "lower", "source": "program_counter",
                         "layer": "engine", "moves": "output_tokens_per_s", "workloads": [cell]}
        ev = Evidence(cell={"name": cell})
        ev.numbers.update({"engine.epochs": 168.0, "engine.epoch_drains": 0.0})
        assert collect_metrics(manifest, ev, traced=True)[name] == {"value": 0.0, "unit": "%"}
        ev.numbers["engine.epoch_drains"] = 42.0
        assert collect_metrics(manifest, ev, traced=True)[name]["value"] == pytest.approx(25.0)
        # the program before the epoch kept the pipeline full had neither counter
        assert name not in collect_metrics(manifest, Evidence(cell={"name": cell}), traced=True)
    # training cells have no entry
    train = Evidence(cell={"name": "bert-base_mlm-s512"})
    train.numbers.update({"engine.epochs": 10.0, "engine.epoch_drains": 1.0})
    assert not {"engine_epoch_drain_share", "engine_epoch_drain_share.mixed"} & set(
        collect_metrics(manifest, train, traced=True))
