"""The readers of the engine's ``serve/`` spans on hand-made host and device
events with known answers, and once on a real (CPU) profiler trace of a tiny
engine, which has the host events but no device plane."""

import json
import types

import pytest

from benchmark.manifest import ROOT, Manifest
from benchmark.readers import serve_spans

NEW = (
    "engine_host_ms_per_step_p50", "engine_token_fetch_wait_ms_p50", "engine_prefill_step_ms_p50",
    "serve_idle_in_launch_share", "serve_idle_in_retire_share",
)
PHASES = (  # name, start and end within a 10 ms step, in ms
    ("serve/admit", 0.1, 0.3), ("serve/grow", 0.4, 0.5), ("serve/decode_launch", 0.6, 1.6),
    ("serve/token_fetch", 1.7, 9.0), ("serve/retire", 9.1, 9.6), ("serve/gauges", 9.7, 9.9),
)


def spec(name):
    return json.loads((ROOT / "benchmark" / "metrics" / f"{name}.json").read_text())


def step(t0, seconds=0.010, phases=PHASES, **labels):
    return [("serve/step", t0, seconds, labels)] + [(n, t0 + 1e-3 * a, 1e-3 * (b - a), {}) for n, a, b in phases]


def busy(*edges):
    """Device operations covering ``edges[0]..edges[1]``, ``edges[2]..edges[3]``, ..."""
    return [("%fusion.1 = bf16[8]{0} fusion(%p)", a, b - a) for a, b in zip(edges[::2], edges[1::2])]


def run_with(monkeypatch, steps):
    monkeypatch.setattr(serve_spans, "host_side", lambda trace_dir: serve_spans.HostSide(steps, [], []))
    return types.SimpleNamespace(trace_dir=None)


def test_a_step_cut_by_the_slice_and_children_without_a_parent_are_left_out():
    orphan_before = ("serve/token_fetch", 0.001, 0.004, {})  # its step began before the profiler did
    orphan_after = ("serve/admit", 0.0301, 0.0002, {})  # its step had not ended when the profiler stopped
    other_thread = [("serve/gauges", 0.0125, 0.0001, {})]  # inside a step's time, on another thread
    steps = serve_spans.whole_steps([[orphan_before, *step(0.006, step=7), *step(0.018, step=8), orphan_after], other_thread])
    assert [s.labels["step"] for s in steps] == [7, 8]
    assert [[c[0] for c in s.children] for s in steps] == [[p[0] for p in PHASES]] * 2
    assert steps[0].child_seconds(["serve/token_fetch", "serve/retire"]) == pytest.approx(7.8e-3)
    assert steps[0].count("serve/prefill_launch") == 0 and steps[1].count("serve/grow") == 1


def test_an_idle_stretch_is_shared_out_over_the_spans_it_covers():
    steps = serve_spans.whole_steps([step(0.000) + step(0.012) + step(0.024)])
    ops = busy(
        0.0, 0.0010,  # 0.40 ms idle, all of it inside step 0's serve/decode_launch
        0.0014, 0.0091,  # 0.60 ms: 0.50 in its serve/retire, 0.10 after it (the step's own time)
        0.0097, 0.0110,  # 1.40 ms: 1.0 between the steps (the caller's loop), then 0.2 own time and 0.2 serve/admit of step 1
        0.0124, 0.01998,  # 20 us: under the 50 us rule
        0.0200, 0.02172,  # 0.16 ms in step 1's serve/gauges
        0.02188, 0.02442,  # 60 us in step 2's serve/grow
        0.02448, 0.0270,  # 3.0 ms in its serve/token_fetch
        0.0300, 0.03392,  # 70 us after its last child: the step's own time
        0.03399, 0.0345,
    )
    idle = serve_spans.idle_by_span(steps, ops)
    assert idle == {
        "serve/decode_launch": pytest.approx(0.40e-3), "serve/retire": pytest.approx(0.50e-3),
        serve_spans.OUTSIDE: pytest.approx(1.0e-3), "serve/admit": pytest.approx(0.20e-3),
        serve_spans.SMALL: pytest.approx(20e-6), "serve/gauges": pytest.approx(0.16e-3), "serve/grow": pytest.approx(60e-6),
        "serve/token_fetch": pytest.approx(3.0e-3), "serve/step": pytest.approx(0.37e-3),
    }
    assert sum(idle.values()) == pytest.approx(0.0345 - sum(d for _, _, d in ops))  # nothing lost, nothing counted twice
    launch, retire = spec("serve_idle_in_launch_share"), spec("serve_idle_in_retire_share")
    assert sum(idle.get(n, 0.0) for n in launch["spans"]) == pytest.approx(0.66e-3)
    assert sum(idle.get(n, 0.0) for n in retire["spans"]) == pytest.approx(0.66e-3)
    text = serve_spans.table(steps, idle, 0.0345, launch["log_table"])
    assert "launch 1.913% + retire 1.913% + rest 12.725%" in text and "= 16.551% of the slice" in text
    assert "0: 100.0% (3, p50 10.00 ms)" in text and "children cover 93.00% of a step" in text
    # the device's clock 0.5 ms behind the host's: the first stretch leaves 0.1 ms in the launch (the rest moves into
    # the token fetch), the third brings 0.3 ms and the sixth its 60 us
    shifted = serve_spans.idle_by_span(steps, ops, offset=0.5e-3)
    assert shifted["serve/decode_launch"] == pytest.approx(0.46e-3) and sum(shifted.values()) == pytest.approx(sum(idle.values()))


def test_the_device_clock_is_put_on_the_host_clock_by_what_has_to_hold():
    """Twelve programs whose device times read 1.5 ms early: the host began to
    issue each 0.2-0.3 ms before it started and saw it done 0.1-0.4 ms after it
    ended, so 1.3 to 1.6 ms may be added. One program of the slice was issued
    before the profiler began and one was not yet done when it stopped."""
    modules = [("jit_decode_step(1)", 0.040 * k, 0.033) for k in range(12)]
    issued = [0.040 * k + 1.5e-3 - (0.2e-3 if k % 3 else 0.3e-3) for k in range(1, 12)]
    done = [0.040 * k + 0.033 + 1.5e-3 + (0.1e-3 if k == 4 else 0.4e-3) for k in range(11)]
    least, most = serve_spans.clock_offset(issued, done, modules)
    assert (least, most) == (pytest.approx(1.3e-3), pytest.approx(1.6e-3))
    assert serve_spans.clock_offset([], [], modules) is None  # another runtime: no such events
    assert serve_spans.clock_offset(issued, [d - 1e-3 for d in done], modules) is None  # done before issued: they disagree


def twelve_steps():
    """Every fourth step carries a prefill chunk and is 1.5 ms longer; the
    first and the ninth also fetch a first token (1 ms) and retire twice."""
    events = []
    for i in range(12):
        phases = list(PHASES)
        seconds = 0.010
        if i % 4 == 0:
            phases = [PHASES[0], ("serve/prefill_launch", 0.31, 0.39), *PHASES[1:]]
            seconds = 0.0115
        if i % 8 == 0:
            phases[2:2] = [("serve/first_token_fetch", 10.0, 11.0), ("serve/retire", 11.0, 11.2)]
        events += step(0.020 * i, seconds, phases, step=i)
    return serve_spans.whole_steps([events])


def test_host_time_fetch_wait_and_the_step_that_carries_a_chunk(monkeypatch):
    steps = twelve_steps()
    run = run_with(monkeypatch, steps)
    # 10 ms less the 7.3 ms token fetch; the chunk steps 11.5 less 7.3, two of them less 1 ms more
    assert sorted(round(1e3 * (s.seconds - s.child_seconds(spec(NEW[0])["minus"])), 6) for s in steps) == [2.7] * 9 + [3.2, 3.2, 4.2]
    assert serve_spans.read(run, None, spec("engine_host_ms_per_step_p50"), "") == pytest.approx(2.7)
    assert serve_spans.read(run, None, spec("engine_token_fetch_wait_ms_p50"), "") == pytest.approx(7.3)
    assert serve_spans.read(run, None, spec("engine_prefill_step_ms_p50"), "") == pytest.approx(11.5)
    no_chunk = [s for s in steps if not s.count("serve/prefill_launch")] * 2
    assert serve_spans.read(run_with(monkeypatch, no_chunk), None, spec("engine_prefill_step_ms_p50"), "") is None


def test_idle_shares_through_read_and_nothing_to_read(monkeypatch, capsys):
    steps = twelve_steps()
    ops = busy(0.0, 0.0208, 0.0212, 0.0492, 0.0494, 0.2315)  # 0.4 ms in step 1's decode_launch, 0.2 ms in step 2's retire
    trace = types.SimpleNamespace(devices=[types.SimpleNamespace(ops=ops, modules=[])], window_s=0.2315)
    run = run_with(monkeypatch, steps)
    assert serve_spans.read(run, trace, spec("serve_idle_in_launch_share"), "") == pytest.approx(100 * 0.4e-3 / 0.2315)
    printed = capsys.readouterr().err
    assert "steps by prefill chunks carried: 0: 75.0% (9, p50 10.00 ms), 1: 25.0% (3, p50 11.50 ms)" in printed
    assert "device clock taken as the host clock" in printed
    assert serve_spans.read(run, trace, spec("serve_idle_in_retire_share"), "") == pytest.approx(100 * 0.2e-3 / 0.2315)
    assert capsys.readouterr().err == ""  # the table is printed once a run
    for name in NEW:  # fewer than ten whole steps, or a program without the spans
        assert serve_spans.read(run_with(monkeypatch, steps[:9]), trace, spec(name), "") is None
        assert serve_spans.read(run_with(monkeypatch, []), trace, spec(name), "") is None


def test_no_trace_under_the_directory_reads_as_nothing(tmp_path):
    assert serve_spans.host_side(tmp_path).steps == []
    assert serve_spans.read(types.SimpleNamespace(trace_dir=tmp_path), None, spec(NEW[0]), "") is None


def test_the_five_are_read_in_the_serving_cell_alone_and_moved_nothing_else():
    m = Manifest()
    assert [n for n in m.per_layer][-5:] == list(NEW)
    for name in NEW:
        assert name in m.cell_per_layer("lm-serve-chat")
        assert not any(name in m.cell_per_layer(c) for c in m.cells if c != "lm-serve-chat")
        assert m.metric_file(name)["reader"] == "serve_spans" and m.per_layer[name]["source"] == "program_span"


def test_a_real_trace_of_a_tiny_engine_gives_whole_tiled_steps(tmp_path):
    """The program's side and the reader's side of the names, the labels and
    the nesting, through ``jax.profiler.ProfileData``."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import program, trace, weights
    from benchmark.tests import tiny
    from deeplearning_mpi_tpu.serving.engine import EngineConfig, ServingEngine

    cfg = json.loads((tiny.make_root(tmp_path) / "benchmark/configs/mistral-7b-v0.1-d8.json").read_text())
    params = weights.build(cfg, weights.seed_words(5), jnp.float32)
    engine = ServingEngine(
        program.model_config(cfg), params, EngineConfig(**{k: v for k, v in cfg["engine"].items() if k != "why"}),
        dtype=jnp.float32, clock=time.monotonic,
    )
    engine.warmup()
    trace.start(tmp_path / "trace")
    engine.submit(np.arange(1, 21, dtype=np.int32), 14)  # two chunks of 16, then 13 decode steps
    engine.run_until_idle()
    jax.profiler.stop_trace()
    steps = serve_spans.host_side(tmp_path / "trace").steps
    assert len(steps) == 14 and [s.labels["step"] for s in steps] == list(range(14))
    assert [s.count("serve/prefill_launch") for s in steps] == [1, 1] + [0] * 12
    assert steps[1].count("serve/first_token_fetch") == 1 and steps[1].count("serve/retire") == 2
    assert all(s.child_seconds(c[0] for c in s.children) <= s.seconds for s in steps)
    assert abs((steps[-1].labels["t"] - steps[0].labels["t"]) - (steps[-1].start - steps[0].start)) < 1e-3
    run = types.SimpleNamespace(trace_dir=tmp_path / "trace")
    assert serve_spans.read(run, None, spec("engine_prefill_step_ms_p50"), "") > 0
    assert 0 < serve_spans.read(run, None, spec("engine_host_ms_per_step_p50"), "") < 1e3 * max(s.seconds for s in steps)
