"""The trace reduction on a trace written by hand: busy and idle time,
the step executable's calls and time, op totals, and idle gaps named
after the host span that overlaps them."""
import pytest

from bench import trace


def planes(extra_device=None):
    host = {"name": "/host:CPU", "lines": {"python": [
        ("bench.window", 1000.0, 9000.0),
        ("report", 2000.0, 1000.0),
        ("admit", 6000.0, 500.0),
        ("unrelated", 8100.0, 100.0),
    ]}}
    dev = {"name": "/device:TPU:0", "lines": {
        "XLA Ops": [("fusion.1", 500.0, 1000.0),     # starts before window
                    ("fusion.2", 1400.0, 400.0),     # overlaps the first
                    ("conv", 3500.0, 2000.0),
                    ("fusion.1", 7000.0, 1000.0),
                    ("fusion.3", 9500.0, 1000.0)],   # ends after window
        "XLA Modules": [("jit_step(3)", 500.0, 400.0),
                        ("jit_step(3)", 1000.0, 800.0),
                        ("jit_step(3)", 3500.0, 2000.0),
                        ("jit_other(4)", 7000.0, 1000.0)],
    }}
    custom = {"name": "/device:CUSTOM:Megascale Trace", "lines": {}}
    out = [host, custom, dev]
    if extra_device is not None:
        out.append(extra_device)
    return out


def test_busy_idle_steps_ops_and_gaps():
    r = trace.reduce_trace(planes(), host_spans=("report", "admit"))
    # union of ops clipped to [1000, 10000]:
    # [1000,1800] [3500,5500] [7000,8000] [9500,10000] = 4300 ns
    assert r["window_s"] == pytest.approx(9000e-9)
    assert r["busy_s"] == pytest.approx(4300e-9)
    assert r["step_calls"] == 2
    assert r["step_s"] == pytest.approx(2800e-9)
    assert r["ops"] == [("conv", pytest.approx(2000e-9)),
                        ("fusion.1", pytest.approx(1000e-9)),
                        ("fusion.3", pytest.approx(1000e-9)),
                        ("fusion.2", pytest.approx(400e-9))]
    # gaps [1800,3500] (report), [5500,7000] (admit), [8000,9500] (none)
    assert r["gaps"] == [("report", pytest.approx(1700e-9)),
                         ("admit", pytest.approx(1500e-9)),
                         ("engine host", pytest.approx(1500e-9))]


def test_step_ops_are_the_ops_inside_the_counted_calls():
    """A call that starts before the window is not counted, nor are its
    ops inside the window; a counted call's op past the window's end is."""
    host = {"name": "/host:CPU", "lines": {"python": [
        ("bench.window", 1000.0, 9000.0)]}}
    dev = {"name": "/device:TPU:0", "lines": {
        "XLA Ops": [("k", 800.0, 100.0), ("k", 1100.0, 300.0),
                    ("k", 2000.0, 100.0), ("j", 2200.0, 100.0),
                    ("k", 9950.0, 100.0), ("k", 10500.0, 200.0)],
        "XLA Modules": [("jit_step(3)", 700.0, 800.0),
                        ("jit_step(3)", 1900.0, 500.0),
                        ("jit_step(3)", 9900.0, 900.0)]}}
    r = trace.reduce_trace([host, dev])
    assert r["step_calls"] == 2
    assert r["ops"] == [("k", pytest.approx(500e-9)),
                        ("j", pytest.approx(100e-9))]
    assert r["step_ops"] == [("k", pytest.approx(400e-9)),
                             ("j", pytest.approx(100e-9))]


def test_busy_is_averaged_over_devices():
    other = {"name": "/device:TPU:1",
             "lines": {"XLA Ops": [("fusion.9", 1000.0, 9000.0)]}}
    r = trace.reduce_trace(planes(other))
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((4300e-9 + 9000e-9) / 2)
    assert r["step_calls"] == 1          # two calls on one device of two


def test_union_and_gaps():
    assert trace.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert trace.gaps([(1, 4), (5, 6)], 0, 8) == [(0, 1), (4, 5), (6, 8)]


def test_a_trace_without_window_or_device_is_refused():
    with pytest.raises(ValueError):
        trace.reduce_trace(planes()[1:])
    with pytest.raises(ValueError):
        trace.reduce_trace(planes()[:2])


def test_device_planes():
    assert trace.is_device("/device:TPU:0")
    assert trace.is_device("/device:TPU:3")
    assert not trace.is_device("/device:CUSTOM:Megascale Trace")
    assert not trace.is_device("/device:CPU:0")
    assert not trace.is_device("/host:CPU")


# --- attention_roofline.lm on a trace written by hand ----------------------
def _hlo(name, shape="bf16[2,1,32,2048,96]{4,3,2,1,0:T(8,128)(2,1)}"):
    """An op named as the chip's trace names it: its HLO text."""
    return (f"%{name} = {shape} custom-call(%copy_bitcast_fusion.2, "
            f"%copy_bitcast_fusion.1, %copy_bitcast_fusion), "
            f"custom_call_target=\"tpu_custom_call\", "
            f"operand_layout_constraints={{{shape}, {shape}, {shape}}}")


FWD = _hlo("vmap_jvp_jit_flash_attention___.1")
DKV = _hlo("flash_mha_bwd_dkv_block_q_major_512_block_q_512_block_k_major_"
           "1024_block_k_1024.1")
DQ = _hlo("flash_mha_bwd_dq_block_q_major_1024_block_k_major_512_block_k_"
          "512.1")
# a layout change around the kernel: its metadata names the kernel's
# scope inside the first 160 characters, its instruction does not
NEAR = ("%copy_bitcast_fusion.1 = bf16[2,32,2048,96]{3,2,1,0} "
        "fusion(%custom-call), kind=kLoop, "
        "metadata={op_name=\"jit(jit_step)/flash_attention/transpose\"}")


def kernel_planes(ops):
    """Two step calls in the window, and ``ops`` as (name, ms) on the
    device, one after the other."""
    host = {"name": "/host:CPU", "lines": {"python": [
        ("bench.window", 0.0, 100e6)]}}
    events, t = [], 1e6
    for name, ms in ops:
        events.append((name, t, ms * 1e6))
        t += ms * 1e6
    dev = {"name": "/device:TPU:0", "lines": {
        "XLA Ops": events,
        "XLA Modules": [("jit_step(7)", 1e6, 40e6),
                        ("jit_step(7)", 50e6, 40e6)]}}
    return [host, dev]


def attention_ctx(reduced):
    from types import SimpleNamespace

    from bench import harness
    root = harness.ROOT
    cell = SimpleNamespace(
        config=harness.read_json(root, "bench/configs/phi3-mini-3.8b-1L.json"),
        traffic=harness.read_json(root, "bench/traffic/lm-ht2.json"))
    return {"cell": cell, "trace": reduced,
            "flops": harness.load_module(
                f"{root}/bench/flops/lm.py", "bench_flops_lm"),
            "peaks": harness.peaks(root, "TPU v5 lite")}


def attention_reader():
    from bench import harness
    return harness.load_module(
        f"{harness.ROOT}/bench/metrics/attention_roofline.lm.py",
        "bench_metric_attention_roofline_lm")


def test_attention_roofline_from_kernel_durations():
    ops = [(FWD, 0.82), (DKV, 1.50), (DQ, 1.22), (NEAR, 0.4),
           ("%fusion.38 = f32[2,32064,3072]", 5.0)] * 2
    reduced = trace.reduce_trace(kernel_planes(ops))
    assert "flash_attention" in NEAR[:trace.OP_NAME_CHARS]
    assert trace.kernel_s(reduced["step_ops"], attention_reader().KERNELS) \
        == pytest.approx(2 * 3.54e-3)
    ctx = attention_ctx(reduced)
    reader = attention_reader()
    # 154.69 GFLOP at 197 TFLOP/s over 3.54 ms of kernels a step
    least = 3 * 2 * 3072 * 2049 * 4096 / 197e12
    assert reader.read(ctx) == pytest.approx(100 * least / 3.54e-3)
    assert 20 < reader.read(ctx) < 25
    assert reader.note(ctx).startswith("compute-bound")


def test_attention_roofline_is_nothing_without_flash_ops():
    reader = attention_reader()
    ops = [(NEAR, 0.4), ("%while.70 = (s32[]) while(%tuple)", 11.0)]
    reduced = trace.reduce_trace(kernel_planes(ops))
    assert trace.kernel_s(reduced["step_ops"], ("flash_attention",)) is None
    assert reader.read(attention_ctx(reduced)) is None
    assert reader.read(attention_ctx(None)) is None


def test_kernel_name_matches_after_the_cut():
    """The name is cut at 160 characters; the instruction's name leads
    it, so the kernel is still found, and only by its instruction."""
    long_dq = _hlo("flash_mha_bwd_dq_block_q_major_1024_block_k_major_512_"
                   "block_k_512.1") + " backend_config=" + "x" * 400
    assert len(long_dq) > trace.OP_NAME_CHARS
    reduced = trace.reduce_trace(kernel_planes([(long_dq, 2.0)]))
    ((name, _),) = reduced["ops"]
    assert len(name) == trace.OP_NAME_CHARS
    assert trace.kernel_s(reduced["ops"], ("flash_mha_bwd_dq",)) == \
        pytest.approx(2e-3)
    assert trace.instruction(name).startswith("flash_mha_bwd_dq_")
    assert trace.instruction("fusion.1") == "fusion.1"
