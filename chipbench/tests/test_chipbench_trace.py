"""The reduction from trace events to busy time, kernel time and the
breakdown, on a small synthetic event list."""

import pytest

from chipbench.trace import Event, reduce_trace, short_name, union

DEV = "/device:TPU:0"
MS = 1e6  # ns


def synthetic():
    """Two launches of the comb program and one of another, on one chip,
    inside harness annotations:

        device ops   |##comb 2ms##|    gap 6ms     |#c 1ms#|#x 1ms#| gap 4ms |##comb 2ms##|
        t (ms)       10          12                18      19      20        24          26
    """
    ops = [
        Event(DEV, "XLA Ops", "custom-call.1", 10 * MS, 2 * MS),
        Event(DEV, "XLA Ops", "custom-call.1", 18 * MS, 1 * MS),
        Event(DEV, "XLA Ops", "fusion.7", 19 * MS, 1 * MS),
        # an op nested inside another must not be counted twice
        Event(DEV, "XLA Ops", "fusion.7", 19.2 * MS, 0.5 * MS),
        Event(DEV, "XLA Ops", "custom-call.1", 24 * MS, 2 * MS),
    ]
    modules = [
        Event(DEV, "XLA Modules", "jit_ecdsa_verify_comb(123)", 10 * MS, 2 * MS),
        Event(DEV, "XLA Modules", "jit_ecdsa_verify_comb(123)", 18 * MS, 1 * MS),
        Event(DEV, "XLA Modules", "jit_convert(9)", 19 * MS, 1 * MS),
        Event(DEV, "XLA Modules", "jit_ecdsa_verify_comb(123)", 24 * MS, 2 * MS),
    ]
    host = [
        Event("/host:CPU", "python", "chipbench.poll", 0, 1 * MS),
        Event("/host:CPU", "python", "chipbench.submit", 1 * MS, 4 * MS),
        Event("/host:CPU", "python", "chipbench.yield", 5 * MS, 16 * MS),
        Event("/host:CPU", "python", "chipbench.poll", 21 * MS, 2.5 * MS),
        Event("/host:CPU", "python", "chipbench.yield", 23.5 * MS, 6.5 * MS),
        Event("/host:CPU", "python", "PjitFunction(f)", 9 * MS, 1 * MS),
        Event("/host:metadata", "x", "whatever", 0, 100 * MS),
    ]
    return ops + modules + host


def test_short_name_keeps_instruction_and_shape():
    hlo = ("%ecdsa_verify_comb.1 = u32[1,512]{1,0:T(1,128)} custom-call("
           "s32[1,64]{1,0:T(1,128)S(1)} %copy-done.4, u32[16,512]{1,0})")
    assert short_name(hlo) == "ecdsa_verify_comb.1 u32[1,512]"
    assert short_name("%copy-start.2 = (s32[128]{0}, u32[]) copy-start(x)") \
        == "copy-start.2 (s32[128]"
    assert short_name("fusion.7") == "fusion.7"
    assert len(short_name("x" * 500)) == 120


def test_union_merges_overlaps_and_nesting():
    assert union([(0, 2), (1, 3), (5, 6), (5.2, 5.5), (9, 9)]) == [
        [0, 3], [5, 6]]


def test_busy_time_kernel_time_and_breakdown():
    s = reduce_trace(synthetic())
    assert s.devices == [DEV]
    assert s.device_events == 5
    # union of op intervals: 2 + (1 + 1) + 2 ms; the nested op adds nothing
    assert s.busy_s == pytest.approx(6e-3)
    # first harness event (0) to the last (30 ms)
    assert s.span_s == pytest.approx(30e-3)
    # kernel time from the per-launch line, matched by name
    secs, launches = s.kernel_seconds("comb")
    assert secs == pytest.approx(5e-3) and launches == 3
    assert s.kernel_seconds("no-such-kernel") == (0.0, 0)
    # device operations by summed time
    assert s.device_ops[0] == ["custom-call.1", pytest.approx(5e-3)]
    assert s.device_ops[1] == ["fusion.7", pytest.approx(1.5e-3)]
    # idle time, split over the annotations it lies under: 12..18 is all
    # chipbench.yield; 20..24 is 1 ms yield, 2.5 ms poll, 0.5 ms yield; the
    # ends of the annotated span count too: 0..10 (1 ms poll, 4 ms submit,
    # 5 ms yield) and 26..30 (yield)
    gaps = dict(s.idle_gaps)
    assert gaps["chipbench.yield"] == pytest.approx((6 + 1.5 + 5 + 4) * 1e-3)
    assert gaps["chipbench.poll"] == pytest.approx((2.5 + 1) * 1e-3)
    assert gaps["chipbench.submit"] == pytest.approx(4e-3)
    # busy + idle gaps = the annotated span
    assert s.busy_s + sum(gaps.values()) == pytest.approx(s.span_s)


def test_two_chips_average_busy_and_no_device_means_nothing():
    evs = synthetic()
    second = [e._replace(plane="/device:TPU:1") for e in evs
              if e.plane == DEV and e.start_ns < 15 * MS]
    s = reduce_trace(evs + second)
    assert len(s.devices) == 2
    assert s.busy_s == pytest.approx((6e-3 + 2e-3) / 2)
    bare = reduce_trace([e for e in evs if e.plane == DEV])
    assert dict(bare.idle_gaps) == {"unattributed": pytest.approx(10e-3)}
    none = reduce_trace([e for e in evs if e.plane != DEV])
    assert none.devices == [] and none.busy_s == 0.0
    assert none.device_ops == [] and none.idle_gaps == []


def test_without_an_ops_line_the_modules_line_serves():
    evs = [e for e in synthetic() if e.line != "XLA Ops"]
    s = reduce_trace(evs)
    assert s.busy_s == pytest.approx(6e-3)
    assert s.kernel_seconds("COMB")[1] == 3
