"""trace_reduce on a hand-built event list: the union of overlapping
intervals, the idle share, module time, the ranking of operations and the
labelling of idle gaps by the host span that covers them."""

import pytest

from harness import trace_reduce as tr

MS = 1e6  # ns
DEV, HOST = "/device:TPU:0", "/host:CPU"
EVENTS = [
    # two overlapping ops (0-4 and 2-6 ms), a gap, one more (10-12 ms)
    (DEV, "XLA Ops", "fusion.1", 0 * MS, 4 * MS),
    (DEV, "XLA Ops", "copy.2", 2 * MS, 4 * MS),
    (DEV, "XLA Ops", "fusion.1", 10 * MS, 2 * MS),
    # the programs that held them
    (DEV, "XLA Modules", "jit_pipeline", 0 * MS, 6 * MS),
    (DEV, "XLA Modules", "jit_pipeline", 10 * MS, 2 * MS),
    # a line that is neither
    (DEV, "Steps", "step", 0 * MS, 12 * MS),
    # the host: a reduce covers most of the 6-10 ms gap
    (HOST, "python", "broker.reduce", 6.5 * MS, 3 * MS),
    (HOST, "python", "http.parse", 9.5 * MS, 1 * MS),
]


def test_union_counts_overlaps_once():
    assert tr.union_ns([(0, 4), (2, 6), (10, 12)]) == 8
    assert tr.union_ns([(0, 10), (2, 3), (4, 5)]) == 10
    assert tr.union_ns([]) == 0


def test_reduce_busy_idle_modules_and_breakdown():
    out = tr.reduce(EVENTS, window_s=0.020)
    assert out["busy_s"] == pytest.approx(0.008)
    assert out["window_s"] == 0.020
    assert out["modules_s"] == pytest.approx(0.008)
    assert (out["n_ops"], out["n_modules"], out["chips"]) == (3, 2, 1)
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(0.6)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.006)]
    assert out["device_ops"][1] == ["copy.2", pytest.approx(0.004)]
    assert out["idle_gaps"] == [["broker.reduce", pytest.approx(0.004)]]


def test_two_chips_are_averaged():
    second = [("/device:TPU:1", line, n, s, d)
              for p, line, n, s, d in EVENTS if p == DEV and n != "copy.2"]
    out = tr.reduce(EVENTS + second, window_s=0.020)
    assert out["chips"] == 2
    assert out["busy_s"] == pytest.approx((0.008 + 0.006) / 2)


def test_no_device_operation_is_an_error_not_an_idle_share():
    with pytest.raises(ValueError):
        tr.reduce([e for e in EVENTS if e[0] == HOST], window_s=1.0)
    with pytest.raises(ValueError):
        tr.reduce([e for e in EVENTS if e[1] != "XLA Ops"], window_s=1.0)


def test_operation_names_are_cut_to_result_and_opcode():
    long = ("%fusion.5 = (bf16[4,1,100]{2,1,0:T(2,128)(2,1)}, bf16[4]{0}) "
            "fusion(u32[1]{0} %x), kind=kLoop, calls=%fused_computation.1")
    assert tr.short_name(long) == "fusion.5 fusion"
    assert tr.short_name("%vmap__.1 = f32[4,16]{1,0:T(8,128)} "
                         "custom-call(s32[4]{0} %a)") == "vmap__.1 custom-call"
    assert tr.short_name("fusion.1") == "fusion.1"
