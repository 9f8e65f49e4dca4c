"""The trace reduction: busy as the union of device operations inside the
window, kernel time by name, idle gaps labelled by the benchmark's host
spans."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cells, trace_reduce  # noqa: E402

MS = 1_000_000


def test_busy_is_the_union_inside_the_window():
    ops = {"/device:TPU:0": [
        ("fusion.1", 0 * MS, 10 * MS),           # starts before the window
        ("bitlinear_decode", 20 * MS, 10 * MS),
        ("bitlinear_decode", 25 * MS, 10 * MS),  # overlaps the one before
        ("copy.3", 90 * MS, 20 * MS),            # runs past the window
    ]}
    spans = [("bench.window", 5 * MS, 95 * MS),
             ("bench.decode_tick", 40 * MS, 45 * MS),
             ("bench.step", 38 * MS, 50 * MS)]
    red = trace_reduce.reduce_events(ops, spans, 5 * MS, 100 * MS)
    assert red.window_s == pytest.approx(0.095)
    assert red.busy_s == pytest.approx((5 + 15 + 10) * 1e-3)
    assert red.kernel_seconds(("bitlinear",)) == pytest.approx(0.020)
    assert red.op_seconds["copy.3"] == pytest.approx(0.010)
    assert red.top_ops(1)[0][0] == "bitlinear_decode"
    # gaps: 10-20, 35-90 (longest, midpoint 62.5 inside decode_tick)
    assert red.gaps[0] == ("bench.decode_tick", pytest.approx(0.055))
    assert red.gaps[1] == ("bench.window", pytest.approx(0.010))


def test_busy_is_averaged_over_devices():
    ops = {"/device:TPU:0": [("a", 0, 50 * MS)],
           "/device:TPU:1": [("a", 0, 10 * MS)]}
    red = trace_reduce.reduce_events(ops, [], 0, 100 * MS)
    assert red.devices == 2
    assert red.busy_s == pytest.approx(0.030)


def test_no_device_ops_is_all_idle():
    red = trace_reduce.reduce_events({"/device:TPU:0": []}, [], 0, 10 * MS)
    assert red.busy_s == 0.0
    assert red.gaps == [("no bench span", pytest.approx(0.010))]


def test_op_names_are_short_and_containers_hold_no_time_of_their_own():
    text = ('%custom-call.79 = f32[280,2,128,128]{2,3,1,0} custom-call(f32[280]'
            ' %pad), custom_call_target="InvertDiagBlock", api_version=1')
    assert trace_reduce.op_name(text) == "custom-call.79 [InvertDiagBlock]"
    assert trace_reduce.op_name("%fusion.3 = bf16[2] fusion(x)") == "fusion.3"
    ops = {"/device:TPU:0": [("while.19", 0, 10 * MS),
                             ("_bitlinear_jit.2", 2 * MS, 3 * MS)]}
    red = trace_reduce.reduce_events(ops, [], 0, 20 * MS)
    assert red.busy_s == pytest.approx(0.010)
    assert red.op_seconds == {"_bitlinear_jit.2": pytest.approx(0.003)}


SWEEP = ("%sa_sweep_many.7 = (f32[280,4,16]{2,1,0:T(4,128)S(1)}, "
         "f32[280,4,1]{2,1,0:T(4,128)S(1)}) custom-call("
         "f32[280,1,16]{2,1,0:T(1,128)S(1)} %bitcast.462, "
         "f32[280,16,16]{2,1,0:T(8,128)} %p.1, "
         "f32[280,4,16]{2,1,0:T(4,128)S(1)} %p.2, "
         "f32[280,24,4,16]{3,2,1,0:T(4,128)} %transpose.9, "
         "f32[280,1,24]{2,1,0:T(1,128)} %p.3), "
         'custom_call_target="tpu_custom_call", backend_config="f32[1,2]"')


def test_spin_updates_are_counted_from_the_kernels_operands():
    mod = cells.metric_module(ROOT, "ising_Mspin_updates_per_s")
    metric = mod.read
    assert mod.operands(SWEEP)[3] == (280, 24, 4, 16)
    assert mod.updates_per_call(SWEEP) == 280 * 24 * 4 * 16
    assert mod.updates_per_call(SWEEP[:200]) == 0       # a cut text
    ops = {"/device:TPU:0": [(SWEEP, i * MS, MS // 2) for i in range(10)]
           + [("%fusion.2 = f32[8] fusion(f32[8] %a)", 0, 20 * MS)]}
    red = trace_reduce.reduce_events(ops, [], 0, 20 * MS)
    assert red.calls(("sweep_many",)) == {SWEEP: 10}
    got = metric({"trace": red})
    assert got == pytest.approx(10 * 280 * 24 * 4 * 16 / 5e-3 / 1e6)
    none = trace_reduce.reduce_events({"/device:TPU:0": []}, [], 0, MS)
    assert metric({"trace": none}) is None


BITLINEAR = ("%bitlinear_grouped_decode.3 = bf16[32,32,512]{2,1,0:T(8,128)(2,1)}"
             " custom-call(bf16[32,32,1024]{2,1,0:T(8,128)(2,1)} %x, "
             "u8[32,32,4,32,1]{4,3,2,1,0:T(1,128)(4,1)} %mp, "
             "bf16[32,32,4,4,128]{4,3,2,1,0:T(4,128)(2,1)} %C), "
             'custom_call_target="tpu_custom_call", backend_config="f32[1,2]"')


def test_bitlinear_bytes_and_operations_come_from_the_calls_shapes():
    mod = cells.metric_module(ROOT, "bitlinear_roofline")
    nbytes, flops = mod.call_cost(BITLINEAR)
    x, y = 32 * 32 * 1024 * 2, 32 * 32 * 512 * 2
    m, c = 32 * 32 * 4 * 32, 32 * 32 * 4 * 4 * 128 * 2
    assert nbytes == x + y + m + c
    assert flops == 32 * (2 * 32 * 1024 * 4 * 4 + 2 * 32 * 32 * 4 * 4 * 128)
    assert mod.call_cost(BITLINEAR[:150]) is None         # a cut text
    ops = {"/device:TPU:0": [(BITLINEAR, i * MS, MS // 10) for i in range(4)]}
    red = trace_reduce.reduce_events(ops, [], 0, 10 * MS)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    least = max(nbytes / 819e9, flops / 197e12)
    got = mod.read({"trace": red, "peaks": peaks})
    assert got == pytest.approx(100 * 4 * least / (4 * 1e-4))
    none = trace_reduce.reduce_events({"/device:TPU:0": []}, [], 0, MS)
    assert mod.read({"trace": none, "peaks": peaks}) is None


def test_serve_mfu_counts_the_published_models_operations():
    mod = cells.metric_module(ROOT, "serve_mfu")
    conf = {"hidden_size": 8, "num_attention_heads": 2,
            "num_key_value_heads": 1, "head_dim": 4, "num_hidden_layers": 3,
            "num_local_experts": 4, "num_experts_per_tok": 2,
            "intermediate_size": 16, "vocab_size": 10}
    per_token = 3 * (2 * 8 * (2 * 2 * 4 + 2 * 1 * 4)
                     + 2 * 8 * 4 + 2 * 3 * 2 * 8 * 16)
    work = {"tokens": 5, "context": 11, "rows": 2}
    assert mod.flops(conf, work) == 5 * per_token + 11 * 3 * 4 * 2 * 4 \
        + 2 * 2 * 8 * 10
    cell = type("C", (), {"config": conf})
    red = trace_reduce.reduce_events({"/device:TPU:0": [("a", 0, MS)]}, [],
                                     0, 2 * MS)
    got = mod.read({"trace": red, "window": {"work": work}, "cell": cell,
                    "peaks": {"bf16_flops_per_s": 1e9}})
    assert got == pytest.approx(100 * mod.flops(conf, work) / 2e-3 / 1e9)
