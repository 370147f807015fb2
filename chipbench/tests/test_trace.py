"""The reduction from a profiler trace to device numbers."""

import dataclasses
import gzip
import json
import shutil
from pathlib import Path

import jax
import pytest

from chipbench import harness, readers, spec, tracing

# two devices' operations and the benchmark's host spans, by hand
SMALL = {
    "devices": [
        [["fusion.1", 0.0, 1.0], ["decode_attention", 0.5, 1.0],
         ["fusion.2", 3.0, 1.0]],
        [["fusion.1", 0.0, 4.0]],
    ],
    "host": [["loop", 0.0, 5.0], ["decode", 0.2, 2.0], ["admit", 1.6, 1.3]],
    "op_stats": {"decode_attention": {
        "long_name": 'custom-call(), custom_call_target="tpu_custom_call"'}},
}


def test_busy_is_the_union_averaged_over_devices():
    assert tracing.union(SMALL["devices"][0]) == [(0.0, 1.5), (3.0, 4.0)]
    assert tracing.busy_seconds(SMALL, 0.0, 5.0) == pytest.approx(
        (2.5 + 4.0) / 2)
    assert tracing.busy_seconds(SMALL, 1.0, 3.5) == pytest.approx(
        (1.0 + 2.5) / 2)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    gaps = tracing.idle_gaps(SMALL, 0.0, 5.0)
    assert gaps == [("admit", pytest.approx(1.5)), ("loop", pytest.approx(1.0))]


def test_breakdown_sums_by_operation():
    b = tracing.breakdown(SMALL, 0.0, 5.0)
    assert sorted(b["device_ops"], key=lambda e: e[0]) == [
        ["decode_attention", 1.0], ["fusion.1", 1.0], ["fusion.2", 1.0]]
    assert len(b["device_ops"]) == 3 and len(b["idle_gaps"]) == 2


def test_kernel_time_counts_ops_inside_its_spans():
    w = harness.Window(seconds=5.0, admits=[], decodes=[], trace=SMALL,
                       t0=0.0, t1=5.0, config={}, peaks={})
    k = spec.kernel("decode_attention")
    assert [e[0] for e in w.kernel_ops(k)] == ["decode_attention"]
    assert w.busy_s == pytest.approx(3.25)
    assert readers.idle_share(w) == pytest.approx(35.0)


def test_host_timings_read_over_the_whole_measured_window():
    """An admission outside the traced slice still counts: the traced window
    can fall between arrivals."""
    decode = (0.2, 2.2, [100, 50], [True, True])
    w = harness.Window(seconds=5.0, admits=[], decodes=[decode], trace=SMALL,
                       t0=0.0, t1=5.0, config={}, peaks={},
                       run_admits=[(6.0, 6.05, [64, 128]), (7.0, 7.0, [])],
                       run_decodes=[decode, (9.0, 9.1, [1, 1], [True, False])])
    assert readers.admit_ms(w) == pytest.approx(25.0)
    assert readers.decode_step_ms(w) == pytest.approx(1050.0)
    assert readers.admit_ms(dataclasses.replace(w, run_admits=[])) is None


def test_model_flops_do_not_overflow_on_engine_lengths():
    """The engine keeps its cache lengths as int32; a whole model's count
    per token passes 2**31."""
    import numpy as np
    config = spec.load_json(spec.ROOT / "chipbench/configs/minicpm-2b.json")
    w = lambda lengths: harness.Window(
        seconds=1.0, admits=[], decodes=[(0.0, 1.0, lengths, [True] * 2)],
        trace=SMALL, t0=0.0, t1=1.0, config=config, peaks={})
    flops = readers.model_flops(w(np.array([300, 1000], np.int32)))
    assert flops == readers.model_flops(w([300, 1000])) > 2 * 2**31


def test_trace_starts_on_work_and_always_ends(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    t = harness.Tracer(at=1.0, length=2.0)
    t.tick(1.5, busy=False)
    assert calls == []
    t.tick(1.7, busy=True)
    t.tick(3.0, busy=False)
    assert calls == ["start"]
    t.stop()
    t.stop()
    assert calls == ["start", "stop"]
    shutil.rmtree(t.logdir)


@pytest.mark.parametrize("layers", [1, 2])
def test_roofline_reads_only_with_one_operation_per_launch(layers):
    config = {"model": {"n_layers": layers, "n_heads": 4, "n_kv_heads": 4,
                        "head_dim": 64}}
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    decode = (0.2, 2.2, [100, 50], [True, True])
    w = harness.Window(seconds=5.0, admits=[], decodes=[decode], trace=SMALL,
                       t0=0.0, t1=5.0, config=config, peaks=peaks)
    share = readers.roofline(w, "decode_attention")
    if layers == 2:     # two launches expected, one found
        assert share is None
    else:               # bytes bound: 2 * 2 * 4 * 64 * 150 + q and out
        least = (2 * 2 * 4 * 64 * 150 + 2 * 2 * 2 * 4 * 64) / 1e9
        assert share == pytest.approx(least / 1.0 * 100)


def test_recorded_trace_finds_one_decode_kernel_per_layer():
    """A trace cut from a run of minicpm-2b.chat on one v5e chip: one
    admission (whose prefill runs the flash kernel, another Pallas call) and
    the two decode steps after it.  Operation names keep the instruction and
    the custom-call target of the profiler's HLO text."""
    path = Path(__file__).parent / "data" / "minicpm-2b.chat.trace.json.gz"
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    config = spec.load_json(spec.ROOT / "chipbench/configs/minicpm-2b.json")
    loop = trace["host"][0]
    decodes = [(s, s + d, [300] * 8, [True] * 8)
               for name, s, d in trace["host"] if name == "decode"]
    w = harness.Window(seconds=loop[2], admits=[], decodes=decodes,
                       trace=trace, t0=0.0, t1=loop[2], config=config,
                       peaks=spec.peaks("TPU v5 lite"))
    ops = w.kernel_ops(spec.kernel("decode_attention"))
    assert len(ops) == 2 * config["model"]["n_layers"]
    assert {o[0].split(" = ")[0] for o in ops} == {"%closed_call.23"}
    assert 0 < readers.roofline(w, "decode_attention") < 100
    assert 0 < w.busy_s < loop[2]
    top = tracing.breakdown(trace, 0.0, loop[2])["device_ops"][0][0]
    assert top == "%while.17"
