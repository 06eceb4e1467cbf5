"""The xplane → metrics reduction on a small recorded trace
(``data/small_trace.textproto``: two chips with identical timelines, one
host thread; every number below is counted by hand from that file)."""

from pathlib import Path

import pytest

from benchmark import trace_reduce as tr
from benchmark.evidence import Evidence
from benchmark.readers import roofline, trace_device, trace_pattern

US = 1e-6
TRACE = Path(__file__).parent / "data" / "small_trace.textproto"


@pytest.fixture(scope="module")
def reduction():
    from jax.profiler import ProfileData

    return tr.reduce(tr.load(ProfileData.from_text_proto(TRACE.read_text())))


def test_window_is_the_benchmarks_annotation(reduction):
    assert reduction.window == pytest.approx((100 * US, 1100 * US))
    assert reduction.chips == 2


def test_busy_union_and_idle_share(reduction):
    # per chip: 50 (clipped) + 400 (while, nested events not double counted)
    # + 120 + 50 + 50 (clipped) = 670 us of a 1000 us window
    assert reduction.busy_s_by_chip == pytest.approx({0: 670 * US, 1: 670 * US})
    assert reduction.busy_s == pytest.approx(670 * US)
    assert reduction.idle_share == pytest.approx(0.33)


def test_self_times_sum_to_busy(reduction):
    # while.2 lasts 400 us and contains 100 + 150 us of other operations
    assert reduction.op_seconds["while.2"] == pytest.approx(2 * 150 * US)
    assert reduction.op_seconds["fusion.1"] == pytest.approx(2 * 250 * US)
    assert sum(reduction.op_seconds.values()) == pytest.approx(
        sum(reduction.busy_s_by_chip.values())
    )
    assert reduction.top_ops(1)[0][0] == "fusion.1"
    # grouped for the breakdown: per chip, XLA's numbering removed
    assert reduction.top_op_groups(2) == [
        ["fusion x1", pytest.approx(250 * US)], ["while x1", pytest.approx(150 * US)]]


def test_pattern_share(reduction):
    ev = Evidence(cell={}, trace=reduction)
    share = trace_pattern.read({"patterns": [r"custom-call\(", "paged_attention"]}, ev)
    assert share == pytest.approx(100 * 100 / 670)
    assert trace_pattern.read({"patterns": ["no_such_kernel"]}, ev) == 0.0
    assert trace_pattern.read({"patterns": ["x"]}, Evidence({})) is None


def test_collectives_in_flight_and_exposed(reduction):
    in_flight, exposed = reduction.collective_seconds()
    # async all-reduce from its start (620) to its done's end (740), the
    # copy overlapping it; plus the synchronous all-gather (50)
    assert in_flight == pytest.approx(170 * US)
    # the start (10), the done's wait (40) and the all-gather (50)
    assert exposed == pytest.approx(100 * US)
    ev = Evidence(cell={}, trace=reduction)
    assert trace_device.read({"what": "collective_exposed_share"}, ev) == pytest.approx(10.0)
    assert trace_device.read({"what": "idle_share"}, ev) == pytest.approx(33.0)


def test_idle_gaps_are_labelled_by_the_innermost_host_event(reduction):
    gaps = dict(map(tuple, reduction.idle_gaps_by_host_activity()))
    assert gaps == pytest.approx({
        "PjitFunction(step)": 200 * US,      # 850..1050
        "no host event": 80 * US,            # 600..620 and 740..800
        "bench.client_send": 50 * US,        # 150..200
    })


def test_roofline_share_counts_steps_from_the_module_line(reduction):
    ev = Evidence(cell={}, trace=reduction)
    ev.numbers.update({
        # 2 chips x 197 TFLOP/s x 50 us: half of the kernel's 100 us a step
        "context.attention_train_flops": 2 * 197e12 * 50 * US,
        "context.attention_train_bytes": 1.0,
        "context.peak_flops_per_chip": 197e12,
        "context.peak_hbm_bytes_per_s": 819e9,
    })
    spec = {"patterns": [r"custom-call\("], "cost": "attention_train", "step_module": "jit_step"}
    assert roofline.read(spec, ev) == pytest.approx(50.0)
    assert ev.notes["attention_train_roofline_bound"] == "compute"


def test_names_are_the_instruction_not_its_text(reduction):
    assert "attn.3" in reduction.op_seconds
    assert "custom-call(" in reduction.op_detail["attn.3"]
    assert tr.short_name("jit_step(123)") == "jit_step(123)"
    assert tr.short_name("bench.trace_window") == "bench.trace_window"


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]
    assert tr.complement([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tr.clip([(0, 3), (5, 7)], 2, 6) == [(2, 3), (5, 6)]


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError, match="no device operation"):
        tr.reduce(tr.Trace({}, {}, []))
