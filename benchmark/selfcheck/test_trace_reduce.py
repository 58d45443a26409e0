"""The reduction from a trace to busy seconds, idle gaps and the top
programs, held against a synthetic trace with a known answer; and the
loader, held against a small trace recorded here."""

import time

import pytest

import trace_reduce

MS = 1e6   # ns


def synthetic():
    # one device, a 100 ms span starting at 1000 ms on the trace's clock:
    # ops at [10,30) [20,40) (overlapping: union 30 ms),
    # [60,70) (10 ms), and one that straddles the end [95,120) (5 inside)
    ops = [("fusion.1", 1010 * MS, 20 * MS), ("fusion.2", 1020 * MS, 20 * MS),
           ("copy.3", 1060 * MS, 10 * MS), ("fusion.1", 1095 * MS, 25 * MS),
           ("before", 900 * MS, 50 * MS)]
    modules = [("jit_apply", 1010 * MS, 30 * MS),
               ("jit_probe", 1060 * MS, 10 * MS),
               ("jit_apply", 1095 * MS, 25 * MS)]
    return {"devices": {"/device:TPU:0": {
        trace_reduce.OPS_LINE: ops, trace_reduce.MODULES_LINE: modules,
        "Steps": [("0", 1000 * MS, 100 * MS)]}},
        "mark_ns": 1000 * MS, "summary": []}


def test_known_busy_idle_and_ops():
    r = trace_reduce.reduce_trace(synthetic(), (1000 * MS, 1100 * MS))
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.045)        # 30 + 10 + 5 ms
    idle_share = 100 * (1 - r["busy_s"] / r["window_s"])
    assert idle_share == pytest.approx(55.0)
    assert r["device_ops"] == [["jit_apply", pytest.approx(0.035)],
                               ["jit_probe", pytest.approx(0.010)]]
    # gaps, longest first: [70,95) 25 ms, [40,60) 20 ms, [0,10) 10 ms
    gaps = [(round((a - 1000 * MS) / MS), round((b - 1000 * MS) / MS))
            for a, b in r["gaps"]]
    assert gaps == [(70, 95), (40, 60), (0, 10)]


def test_nested_ops_count_once():
    # the TPU's operation line nests: a `while` of 40 ms holds its body's
    # four ops of 9 ms each. The device was busy for 40 ms, not 76.
    nested = [("while.7", 1010 * MS, 40 * MS)] + [
        (f"fusion.{i}", (1011 + 10 * i) * MS, 9 * MS) for i in range(4)]
    loaded = {"devices": {"/device:TPU:0": {
        trace_reduce.OPS_LINE: nested,
        trace_reduce.MODULES_LINE: [("jit_ap", 1010 * MS, 40 * MS)]}},
        "mark_ns": 1000 * MS, "summary": []}
    import run
    r = trace_reduce.reduce_trace(loaded, (1000 * MS, 1100 * MS))
    assert r["busy_s"] == pytest.approx(0.040)
    assert r["device_ops"] == [["jit_ap", pytest.approx(0.040)]]
    record = {"trace": {"whole_epochs": {"busy_s": r["busy_s"],
                                         "source_rows": 20000.0}}}
    assert run.load_module("layer_metrics", "kernel_us_per_row").read(
        record) == pytest.approx(2.0)                 # 40 ms / 20,000 rows


def span_and_history(seals_ms, end_ms=100.0):
    """A span of `end_ms` whose mark was made at wall time 5000.0 (1000
    ms on the synthetic trace's clock), the opening checkpoint's epoch
    sealed before it, and an epoch of 10,000 rows sealed at each of
    `seals_ms` after it."""
    span = {"mark_wall": 5000.0, "end_wall": 5000.0 + end_ms / 1e3}
    history = {1: {"ts": 4999.9, "interval_s": 0.05, "source_rows": 7.0}}
    for i, ms in enumerate(seals_ms):
        history[2 + i] = {"ts": 5000.0 + ms / 1e3, "interval_s": 0.04,
                          "source_rows": 10000.0,
                          "phase.device_compute": 0.03,
                          "phase.host_emit": 0.01}
    return span, history


def test_span_starts_before_the_first_seal():
    """The span opens with the window: the device's work before the
    first seal inside it belongs to that seal's epoch, so `whole_epochs`
    runs from the span's start to the last seal and counts the rows of
    every epoch sealed inside: two epochs here, not one (PR 51). The
    last seal at 80 ms cuts the op that starts at 95 ms out."""
    import run
    span, history = span_and_history([45.0, 80.0])
    r = run.reduce_loaded(synthetic(), span, history, {3: 0.015})
    assert r["epochs_in_span"] == 2
    assert r["window_s"] == pytest.approx(0.100)
    assert r["whole_epochs"]["busy_s"] == pytest.approx(0.040)
    assert r["whole_epochs"]["source_rows"] == 20000.0
    assert run.load_module("layer_metrics", "kernel_us_per_row").read(
        {"trace": r}) == pytest.approx(2.0)
    # the gaps are named and add up to the span's idle time: the tail
    # after the last seal is the closing checkpoint's, not a paused
    # program's
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(0.055)
    assert gaps["checkpoint_upload_commit"] == pytest.approx(0.025)
    assert gaps["device_compute"] == pytest.approx(0.030)
    # a window of one barrier has one whole epoch
    span, history = span_and_history([80.0])
    r = run.reduce_loaded(synthetic(), span, history, {})
    assert r["epochs_in_span"] == 1
    assert r["whole_epochs"] == {"busy_s": pytest.approx(0.040),
                                 "source_rows": 10000.0}
    # no mark on the trace's clock: no epoch is placed, nothing to read
    loaded = synthetic()
    loaded["mark_ns"] = None
    r = run.reduce_loaded(loaded, span, history, {})
    assert r["epochs_in_span"] == 0 and "whole_epochs" not in r


def test_two_devices_average():
    loaded = synthetic()
    loaded["devices"]["/device:TPU:1"] = {
        trace_reduce.OPS_LINE: [("fusion.9", 1000 * MS, 100 * MS)]}
    r = trace_reduce.reduce_trace(loaded, (1000 * MS, 1100 * MS))
    assert r["busy_s"] == pytest.approx((0.045 + 0.100) / 2)
    assert r["per_device"]["/device:TPU:1"] == pytest.approx(0.100)


def test_layer_readers_on_the_synthetic_trace():
    import run
    r = trace_reduce.reduce_trace(synthetic(), (1000 * MS, 1100 * MS))
    r["whole_epochs"] = {"busy_s": 0.040, "source_rows": 20000.0}
    record = {"trace": r}
    idle = run.load_module("layer_metrics", "device_idle_share").read(record)
    per_row = run.load_module("layer_metrics", "kernel_us_per_row").read(
        record)
    assert idle == pytest.approx(55.0)
    assert per_row == pytest.approx(2.0)              # 40 ms / 20,000 rows
    assert run.load_module("layer_metrics", "device_idle_share").read(
        {"trace": None}) is None


def test_loader_finds_the_mark_in_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation(trace_reduce.MARK):
        time.sleep(0.001)
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    loaded = trace_reduce.load_xplane(
        trace_reduce.newest_xplane(str(tmp_path)))
    assert loaded["mark_ns"] is not None
    assert any(n for _p, _l, n in loaded["summary"])
    # the CPU has no device plane: the harness then reports no device
    # metric at all, it does not fall back to host events
    assert loaded["devices"] == {}
