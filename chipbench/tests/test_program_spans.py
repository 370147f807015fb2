"""The reduction of the program's own spans and named programs."""

import copy

import jax
import pytest

from chipbench import harness, program, readers, spec, tracing
from chipbench.tests.test_trace import SMALL

# One step in the window [0, 100] (whole numbers, so that sums are exact):
# an admission whose prefill runs on the device over [10, 30] and whose slot
# write runs over [32, 34], then a decode over [50, 80].  Before the window,
# a step whose spans and programs must not count.
SPANS = {
    "devices": [[["%while.16", 10.0, 20.0], ["%copy.2", 32.0, 2.0],
                 ["%while.17", 50.0, 30.0],
                 ["%while.16", -16.0, 2.0], ["%while.17", -9.0, 1.0]]],
    "host": [["loop", 0.0, 100.0], ["admit", 4.0, 41.0],
             ["decode", 48.0, 35.0]],
    "op_stats": {},
    "program": [
        ["engine.step", 5.0, 85.0, {}],
        ["engine.admit", 5.0, 40.0, {}],
        ["engine.prefill", 6.0, 4.0, {"request": 0, "plen": 4}],
        ["engine.write_slot", 32.0, 4.0, {"request": 0, "plen": 4}],
        ["engine.first_token", 36.0, 9.0, {"request": 0, "plen": 4}],
        ["engine.decode", 45.0, 37.0, {"active": 1}],
        ["engine.decode_inputs", 45.0, 3.0, {}],
        ["engine.decode_dispatch", 48.0, 1.0, {}],
        ["engine.decode_wait", 49.0, 33.0, {}],
        ["engine.sample", 82.0, 8.0, {}],
        ["engine.prefill", -19.0, 3.0, {"request": 7, "plen": 4}],
        ["engine.decode", -10.0, 3.0, {"active": 1}],
    ],
    "modules": [[["jit_engine_prefill(1)", 10.0, 20.0],
                 ["jit_dynamic_update_slice(2)", 32.0, 2.0],
                 ["jit_engine_decode(3)", 50.0, 30.0],
                 ["jit_engine_prefill(1)", -16.0, 2.0],
                 ["jit_engine_decode(3)", -9.0, 1.0]]],
}


def window(trace, t0=0.0, t1=100.0):
    return harness.Window(seconds=t1 - t0, admits=[], decodes=[],
                          trace=trace, t0=t0, t1=t1, config={}, peaks={})


def test_idle_is_split_over_time_by_the_innermost_span():
    """The gap over [34, 50] straddles the admission and the decode step:
    each gets the part of it that its spans cover."""
    idle = program.idle_by_span(SPANS, 0.0, 100.0)
    assert idle == {
        "harness": 5.0 + 10.0, "engine.admit": 1.0 + 2.0,
        "engine.prefill": 4.0, "engine.write_slot": 2.0,
        "engine.first_token": 9.0, "engine.decode_inputs": 3.0,
        "engine.decode_dispatch": 1.0, "engine.decode_wait": 1.0 + 2.0,
        "engine.sample": 8.0}
    w = window(SPANS)
    assert sum(idle.values()) == pytest.approx(
        readers.idle_share(w) / 100 * 100.0)


def test_innermost_prefers_the_span_opened_last():
    spans = [["outer", 0.0, 4.0, {}], ["a", 1.0, 2.0, {}],
             ["b", 1.0, 1.0, {}], ["after", 3.5, 1.5, {}]]
    assert program.innermost(spans) == [
        (0.0, 1.0, "outer"), (1.0, 2.0, "b"), (2.0, 3.0, "a"),
        (3.0, 3.5, "outer"), (3.5, 5.0, "after")]


def test_readers_divide_by_spans_that_start_in_the_window():
    w = window(SPANS)
    assert [e[3]["request"]
            for e in program.spans_started(w, "engine.prefill")] == [0]
    assert program.prefill_ms(w) == 20.0 * 1e3
    assert program.decode_device_ms(w) == 30.0 * 1e3
    assert program.admit_idle_ms(w) == (3.0 + 4.0 + 2.0 + 9.0) * 1e3
    assert program.step_idle_ms(w) == (3.0 + 1.0 + 3.0 + 8.0) * 1e3
    # a window that also holds the earlier step counts both
    wide = window(SPANS, t0=-20.0)
    assert program.prefill_ms(wide) == (20.0 + 2.0) / 2 * 1e3
    assert program.decode_device_ms(wide) == (30.0 + 1.0) / 2 * 1e3


def test_module_time_is_read_per_program_name():
    """The slot write's eager program runs inside the admission but is not
    the prefill's."""
    w = window(SPANS)
    assert [m[0] for m in program.modules_started(w, "jit_engine_")] == [
        "jit_engine_prefill(1)", "jit_engine_decode(3)"]
    split = program.admission_split(w)
    assert split["admissions"] == 1
    assert split["device_ms"] == {"jit_engine_prefill": 20000.0,
                                  "jit_dynamic_update_slice": 2000.0}
    assert split["span_ms"] == 40000.0
    assert sum(split["idle_ms"].values()) + 22000.0 == 40000.0


def test_readers_read_nothing_without_program_spans():
    for trace in (SMALL, dict(SPANS, program=[]), dict(SPANS, modules=[])):
        w = window(trace, 0.0, 5.0)
        assert [program.prefill_ms(w), program.decode_device_ms(w),
                program.admit_idle_ms(w), program.step_idle_ms(w),
                program.admission_split(w)] == [None] * 5


def test_program_keys_leave_the_existing_reductions_alone():
    """The existing readers and the breakdown read the same with and
    without the program's events beside the benchmark's."""
    plain = copy.deepcopy(SMALL)
    merged = dict(copy.deepcopy(SMALL), program=SPANS["program"],
                  modules=SPANS["modules"])
    for t0, t1 in ((0.0, 5.0), (1.0, 3.5)):
        assert (tracing.busy_seconds(plain, t0, t1)
                == tracing.busy_seconds(merged, t0, t1))
        assert (tracing.idle_gaps(plain, t0, t1)
                == tracing.idle_gaps(merged, t0, t1))
        assert (tracing.breakdown(plain, t0, t1)
                == tracing.breakdown(merged, t0, t1))
        a, b = window(plain, t0, t1), window(merged, t0, t1)
        assert readers.idle_share(a) == readers.idle_share(b)
        k = spec.kernel("decode_attention")
        assert a.kernel_ops(k) == b.kernel_ops(k)


def test_collect_reads_program_spans_and_their_attributes(tmp_path):
    """On the CPU the trace has no device plane, so no modules; the spans
    keep their attributes as the events' statistics."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("engine.prefill", request=3,
                                          plen=192):
            jax.numpy.ones(4).block_until_ready()
        with jax.profiler.TraceAnnotation("elsewhere"):
            pass
    finally:
        jax.profiler.stop_trace()
    got = program.collect(str(tmp_path))
    assert got["modules"] == []
    assert [(e[0], e[3]) for e in got["program"]] == [
        ("engine.prefill", {"request": 3, "plen": 192})]
    assert got["program"][0][2] > 0
