"""The trace reduction on a hand-made trace with hand-computed answers."""
import pytest

from bench import trace_reduce


def ev(name, start_us, dur_us):
    return (name, start_us * 1e3, dur_us * 1e3)


def trace():
    """Two devices, one host thread with nested bench spans.

    device 0: sweep 0-40us, all-gather 40-50, sweep 60-100, fusion 90-95
              (overlaps the sweep: busy is a union)
    device 1: sweep 10-50, all-gather-start 50-60
    host:     bench.solve 0-120, bench.chunk 0-50, bench.on_record 50-58,
              bench.chunk 58-110
    """
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ev("bench.solve", 0, 120), ev("bench.chunk", 0, 50),
            ev("bench.on_record", 50, 8), ev("bench.chunk", 58, 52),
            ev("other", 0, 120)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [ev("jit_step", 0, 100)]},
            {"name": "XLA Ops", "events": [
                ev("%sweep_rows_gs.2 = f32[8,4] custom-call(s32[8] %p)", 0, 40),
                ev("%all-gather.1 = f32[8,4] all-gather(f32[2,4] %x)", 40, 10),
                ev("%sweep_rows_gs.2 = f32[8,4] custom-call(s32[8] %p)", 60, 40),
                ev("%fusion.2 = f32[8,4] fusion(f32[8,4] %sweep_rows_gs.2)",
                   90, 5)]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [
                ev("%sweep_rows_gs.1 = f32[8,4] custom-call()", 10, 40),
                ev("%all-gather-start = f32[8,4] all-gather-start()", 50,
                   10)]}]},
        {"name": "/device:TPU_NON_CORE:0", "lines": [
            {"name": "XLA Ops", "events": [ev("ignored", 0, 500)]}]},
    ]


def test_busy_ops_collectives():
    s = trace_reduce.reduce(trace())
    assert s["devices"] == 2
    # device 0 busy 0-50 and 60-100 (90 us), device 1 10-60 (50 us)
    assert s["busy_s"] == pytest.approx((90e-6 + 50e-6) / 2)
    assert s["collective_s"] == pytest.approx((10e-6 + 10e-6) / 2)
    assert s["op_s"]["sweep_rows_gs.2"] == pytest.approx(80e-6 / 2)
    assert s["top_ops"][0][0] == "sweep_rows_gs.2"
    # the fusion that reads the kernel's output is not the kernel
    assert trace_reduce.op_seconds(
        s, r"sweep_rows_gs(\.\d+)?") == pytest.approx(120e-6 / 2)
    # the traced window spans 0-120 us (host spans included)
    assert s["traced_s"] == pytest.approx(120e-6)


def test_idle_gaps_labelled_by_innermost_span():
    s = trace_reduce.reduce(trace())
    idle = dict(s["idle_by_span"])
    # device 0 gaps: 50-60 (mid 55: on_record), 100-120 (mid 110: the
    # second chunk ends at 110, so it is still open there)
    # device 1 gaps: 0-10 (mid 5: chunk), 60-120 (mid 90: chunk)
    assert idle["bench.on_record"] == pytest.approx(10e-6 / 2)
    assert idle["bench.chunk"] == pytest.approx((10e-6 + 60e-6 + 20e-6) / 2)
    assert sum(idle.values()) == pytest.approx(2 * 120e-6 / 2 - s["busy_s"])


def test_label_points_unnested_threads():
    spans = sorted([(0, 100, "bench.wait"), (10, 20, "bench.chunk"),
                    (30, 40, "bench.submit")])
    assert trace_reduce.label_points([5, 15, 25, 35, 150], spans) == [
        "bench.wait", "bench.chunk", "bench.wait", "bench.submit",
        trace_reduce.NO_SPAN]


def test_no_device_ops_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce([{"name": "/host:CPU", "lines": []}])


def test_n_devices_limits_the_average():
    s = trace_reduce.reduce(trace(), n_devices=1)
    assert s["devices"] == 1
    assert s["busy_s"] == pytest.approx(90e-6)


def test_readers_on_the_reduced_trace():
    from bench import harness, readers
    s = trace_reduce.reduce(trace(), n_devices=1)
    r = harness.Readings({"updates": 8}, s, {}, None, 120e-6)
    # device 0's kernel time (40 + 40 us) over the updates it applied; the
    # fusion is not the kernel
    assert readers.us_per_update(r) == pytest.approx(80.0 / 8)
    assert readers.idle_pct(r) == pytest.approx(100 * (1 - 90e-6 / 120e-6))
    # no peaks (a CPU run): no roofline share, not a made-up 0
    assert readers.sweep_roofline_pct(r) is None
