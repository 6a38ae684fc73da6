from pathlib import Path

import pytest

from benchmark import trace

FIXTURE = Path(__file__).parent / "fixtures" / "flash_loss_3steps.xplane.pb"


def test_the_recorded_trace_gives_known_numbers():
    """Three executions of a small jitted loss (flash attention forward and
    backward at [1, 4, 1024, 128]) recorded on a TPU v5e, the host waiting on a
    loader between them."""
    t = trace.read(FIXTURE)
    assert len(t.devices) == 1
    assert t.window_s == pytest.approx(6.149823e-3, rel=1e-6)
    assert t.busy_s == pytest.approx(2.10916e-4, rel=1e-5)
    assert t.idle_share == pytest.approx(0.965704, rel=1e-5)
    assert t.module_durations("^jit_loss") == pytest.approx([7.1751e-05, 7.1806e-05, 7.2061e-05])
    assert t.op_seconds(r"^flash_attention\S* \S+ custom-call$") == pytest.approx(1.85734e-4, rel=1e-5)
    name, seconds = t.top_ops(1)[0]
    assert name == "flash_attention_bhsd__.3 bf16[1,4,1024,128] custom-call" and seconds == pytest.approx(7.269e-05)
    gaps = dict(t.idle_gaps())
    assert gaps["bench/loader_next"] == pytest.approx(5.936759e-3, rel=1e-6)
    assert t.exposed_collective_seconds() == 0.0


def test_busy_idle_and_exposed_collective_time_on_a_hand_made_trace():
    ops = [
        ("%fusion.1 = bf16[8,128]{1,0} fusion(%p0)", 0.0, 1.0),
        ("%all-reduce.3 = f32[64]{0} all-reduce(%fusion.1), replica_groups={}", 1.0, 0.5),  # on the lane: exposed
        ("%fusion.2 = bf16[8,128]{1,0} fusion(%p1)", 2.0, 1.0),  # after an idle gap of 0.5
        ("%all-reduce-done.1 = f32[64]{0} all-reduce-done(%ars)", 3.0, 0.25),
    ]
    dev = trace.DeviceTrace(ops, [("jit_step(1)", 0.0, 3.25)])
    t = trace.summarize([dev, dev], [("bench/loader_next", 1.4, 0.7), ("trainer/train_step", 0.0, 5.0)])
    assert t.window_s == pytest.approx(3.25) and t.busy_s == pytest.approx(2.75)
    assert t.exposed_collective_seconds() == pytest.approx(0.75)
    assert t.idle_gaps() == [["bench/loader_next", pytest.approx(0.5)]]
    assert trace.union_seconds([(0, 2), (1, 2), (5, 1)]) == pytest.approx(4.0)
    assert trace.short_name(ops[1][0]) == "all-reduce.3 f32[64] all-reduce"


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        trace.summarize([trace.DeviceTrace([], [])])
