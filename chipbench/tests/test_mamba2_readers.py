"""The readers that ``mamba2-780m.chat`` adds: the ``ssd_scan`` kernel's
count of work and its roofline's count check, and the decode step's memory
roofline, on windows written by hand."""

import jax
import numpy as np
import pytest

from chipbench import harness, spec

KERNEL = 'custom-call(), custom_call_target="tpu_custom_call"'

# two layers, d_model 8, expand 2 (d_inner 16), heads of 4 (4 heads),
# d_state 2, chunk 4
TINY = {
    "family": "ssm", "dtype": "bfloat16",
    "state_dtype": {"h": "float32", "conv": "bfloat16"},
    "model": {"n_layers": 2, "d_model": 8, "vocab": 10,
              "tie_embeddings": True,
              "ssm": {"d_state": 2, "head_dim": 4, "expand": 2, "chunk": 4,
                      "conv_width": 4}},
}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def test_ssd_scan_work_is_the_hand_count():
    """A prompt of 6 tokens is a chunk of 4 and one of 2.  Per layer:
    chunk 4: scores 2·4·4·2 = 64, per head 2·4·4·4 + 2·4·2·4 = 192, so
    64 + 4·192 = 832; chunk 2: 2·2·2·2 = 16, per head 2·2·2·4 + 2·2·2·4 =
    64, so 16 + 4·64 = 272.  Bytes: inputs and outputs 2·6·4·4 = 192, the
    decay operands 3·6·4 = 72, B and C 2·6·2 = 24, two chunks' states
    2·4·2·4 = 64, in all 352 floats of 4 bytes."""
    k = spec.kernel("ssd_scan")
    assert k.prompt_work(6, TINY) == (832.0 + 272.0, 352.0 * 4)
    call = (0.0, 1.0, [6, 6])
    assert k.launches(call, TINY) == 2 * 2
    assert k.work(call, TINY) == (4 * 1104.0, 4 * 1408.0)


def test_ssd_scan_bytes_are_the_kernels_operands():
    """Where the prompt fills its chunks, the bytes counted are those of
    the Pallas call's operands and results."""
    from repro.kernels import ssd_scan
    t, nh, hd, n, c = 8, 4, 4, 2, 4
    f32 = lambda *s: jax.ShapeDtypeStruct((1, t // c) + s, np.float32)
    out = jax.eval_shape(ssd_scan.ssd_intra_chunk, f32(nh, c, hd),
                         f32(nh, c), f32(c, n), f32(c, n))
    operands = 4 * (t * nh * hd + 3 * t * nh + 2 * t * n)
    results = sum(4 * int(np.prod(o.shape)) for o in out)
    assert spec.kernel("ssd_scan").prompt_work(t, TINY)[1] == (
        operands + results)


def _admit_window(kernel_ops: int, shift: float = 0.0,
                  op: str = "%closed_call.3 = " + KERNEL) -> harness.Window:
    """One admission of a 6-token prompt in [1, 2], with ``kernel_ops``
    Pallas calls in it (on the device's clock, ``shift`` seconds later
    than the host's) and an XLA operation."""
    ops = [[op, 1.1 + 0.1 * i + shift, 0.05] for i in range(kernel_ops)]
    ops += [["%fusion.1 = ...", 1.05, 0.02]]
    trace = {"devices": [ops], "op_stats": {},
             "host": [["loop", 0.0, 3.0], ["admit", 1.0, 1.0]]}
    return harness.Window(seconds=3.0, admits=[(1.0, 2.0, [6])], decodes=[],
                          trace=trace, t0=0.0, t1=3.0, config=TINY,
                          peaks=PEAKS)


@pytest.mark.parametrize("found", [1, 2, 3])
def test_ssd_scan_roofline_reads_only_with_one_call_per_layer(found):
    """Two layers make two calls for the prompt; a window holding another
    number of kernel operations is not read."""
    share = spec.reader("ssd_scan_roofline").read(_admit_window(found))
    if found != 2:
        assert share is None
    else:   # bytes bound: 2 · 1408 bytes at 1e9 B/s over 2 · 0.05 s
        assert share == pytest.approx(2 * 1408 / 1e9 / 0.1 * 100)


def test_ssd_scan_roofline_reads_calls_shifted_out_of_their_span():
    """The device's clock can shift against the host's spans: calls that
    start after their admission's span still belong to it."""
    share = spec.reader("ssd_scan_roofline").read(_admit_window(2, 1.5))
    assert share == pytest.approx(2 * 1408 / 1e9 / 0.1 * 100)


# the HLO text of a call whose two results and first operand XLA staged in
# VMEM (S(1)); the second operand, as large as the first, lies in HBM
STAGED = ("%ssd_scan.4 = (f32[1,1,4,4,4]{4,3,2,1,0:T(8,128)S(1)}, "
          "f32[1,1,4,2,4]{4,3,2,1,0:T(8,128)S(1)}) custom-call("
          "f32[1,1,4,4,4]{4,3,2,1,0:T(8,128)S(1)} %a, "
          "f32[1,1,4,4,4]{4,3,2,1,0:T(8,128)} %b), " + KERNEL.split(", ")[1])


def test_ssd_scan_hbm_share_leaves_out_arrays_staged_in_vmem():
    """Of 64 + 32 + 64 + 64 floats, the 64 of the unstaged operand lie in
    HBM; a text without arrays counts every byte."""
    k = spec.kernel("ssd_scan")
    assert k.hbm_share(STAGED) == pytest.approx(64 / 224)
    assert k.hbm_share(STAGED.replace("S(1)", "")) == 1.0
    assert k.hbm_share("%closed_call.3 = " + KERNEL) == 1.0
    all_staged = STAGED.replace("T(8,128)}", "T(8,128)S(1)}")
    assert k.hbm_share(all_staged) == 0.0
    # with every array staged the call is bound by its operations:
    # 2 · 1104 at 1e12 FLOP/s over 2 · 0.05 s
    share = spec.reader("ssd_scan_roofline").read(
        _admit_window(2, op=all_staged))
    assert share == pytest.approx(2 * 1104 / 1e12 / 0.1 * 100)


def test_decode_roofline_is_least_bytes_over_decode_device_time():
    """Device time is the union of the operations that start in a decode
    span (a loop and the body nested in it count once; an admission's
    operation does not count); the least bytes are the weights once and
    each active slot's state read and written."""
    reader = spec.reader("decode_roofline.mamba2-chat")
    d, di, n, nh, hd, v = 8, 16, 2, 4, 4, 10
    # embedding, the final norm, and per layer: ln1 (float32), w_in, conv,
    # A_log, D, dt_bias, the gate norm and w_out in bf16
    weights = 2 * v * d + 4 * d + 2 * (4 * d + 2 * (
        d * (2 * di + 2 * n + nh) + 4 * (di + 2 * n) + 3 * nh + di + di * d))
    slot = 2 * (4 * nh * hd * n + 2 * 3 * (di + 2 * n))
    assert reader.weight_bytes(TINY) == weights
    assert reader.slot_state_bytes(TINY) == slot
    ops = [["%while.1 = ...", 0.1, 1.0], ["%fusion.2 = ...", 0.2, 0.5],
           ["%fusion.3 = ...", 1.2, 0.4], ["%fusion.4 = ...", 2.6, 0.3]]
    trace = {"devices": [ops], "op_stats": {},
             "host": [["loop", 0.0, 3.0], ["decode", 0.0, 2.0],
                      ["admit", 2.5, 0.5]]}
    w = harness.Window(
        seconds=3.0, admits=[], trace=trace, t0=0.0, t1=3.0, config=TINY,
        peaks=PEAKS, decodes=[(0.0, 2.0, [5, 1, 9], [True, False, True])])
    least = weights + 2 * 2 * slot
    # the loop [0.1, 1.1] holds [0.2, 0.7]; with [1.2, 1.6], 1.4 s
    assert reader.read(w) == pytest.approx(least / 1e9 / 1.4 * 100)
    empty = harness.Window(seconds=3.0, admits=[], decodes=[], trace=trace,
                           t0=0.0, t1=3.0, config=TINY, peaks=PEAKS)
    assert reader.read(empty) is None
