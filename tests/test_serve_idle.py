"""``benchmark/readers/serve_idle.py`` on hand-made host and device events with
known answers: device idle given to the innermost span inside the launch and
the fetch (``launch/``, ``fetch/``: ``serving/launch.py``), the metrics
``serve_idle_in_{prep,h2d,dispatch,return}_share`` read through their files,
and nothing read from a program that writes no ``launch/`` span."""

import json
import types

import pytest

from benchmark.manifest import ROOT, Manifest
from benchmark.readers import serve_idle, serve_spans

STEP = "serve/step"
LAUNCH, FETCH = ("serve/step", "serve/decode_launch"), ("serve/step", "serve/token_fetch")
PHASES = (  # name, start and end within a 10 ms step, in ms; launch/ and fetch/ inside their parents
    ("serve/admit", 0.1, 0.3),
    ("serve/decode_launch", 0.6, 1.6), ("launch/prep", 0.65, 0.95), ("launch/h2d", 1.0, 1.2), ("launch/dispatch", 1.25, 1.5),
    ("serve/token_fetch", 1.7, 9.0), ("fetch/ready", 1.72, 8.8), ("fetch/d2h", 8.82, 8.98),
    ("serve/retire", 9.1, 9.6), ("serve/gauges", 9.7, 9.9),
)
BUSY = (0.0, 0.7, 1.1, 1.3, 1.9, 8.85, 9.15, 9.17, 9.20, 10.3)  # device busy from, to, from, to ... in ms of a step
IDLE = {  # what BUSY leaves idle in a step, by the innermost span over it (seconds); then 1.7 ms after the step
    (*LAUNCH, "launch/prep"): 0.25e-3, LAUNCH: 0.15e-3, (*LAUNCH, "launch/h2d"): 0.10e-3,
    (*LAUNCH, "launch/dispatch"): 0.20e-3, (STEP,): 0.20e-3, FETCH: 0.04e-3, (*FETCH, "fetch/ready"): 0.18e-3,
    (*FETCH, "fetch/d2h"): 0.13e-3, (STEP, "serve/retire"): 0.05e-3, (serve_spans.SMALL,): 0.03e-3,
}
PERIOD = 12e-3
NEW = ("serve_idle_in_prep_share", "serve_idle_in_h2d_share", "serve_idle_in_dispatch_share", "serve_idle_in_return_share")


def spec(name):
    return json.loads((ROOT / "benchmark" / "metrics" / f"{name}.json").read_text())


def step(t0, phases=PHASES):
    return [(STEP, t0, 0.010, {})] + [(n, t0 + 1e-3 * a, 1e-3 * (b - a), {}) for n, a, b in phases]


def busy(t0):
    return [("%fusion.1 = bf16[8]{0} fusion(%p)", t0 + 1e-3 * a, 1e-3 * (b - a)) for a, b in zip(BUSY[::2], BUSY[1::2])]


def steps_and_ops(n, phases=PHASES):
    """``n`` steps ``PERIOD`` apart, each with ``BUSY``, and the device busy
    at the very end: 1.7 ms of idle after each step but the last."""
    events = [e for i in range(n) for e in step(PERIOD * i, phases)]
    ops = [op for i in range(n) for op in busy(PERIOD * i)] + [("%end", PERIOD * n - 0.2e-3, 0.2e-3)]
    return events, ops


def test_nesting_by_containment_leaves_out_what_no_whole_step_holds():
    before = ("launch/h2d", -0.004, 0.001, {})  # its step began before the profiler did
    after = ("launch/prep", 0.025, 0.001, {})  # its step had not ended when the profiler stopped
    other_thread = [("serve/gauges", 0.003, 1e-4, {})]  # inside a step's time, on another thread
    steps = serve_idle.whole_steps([[before, *step(0.0), *step(0.012), after], other_thread])
    assert [s.start for s in steps] == [0.0, 0.012]
    tree = [(p, n.seconds) for p, n in steps[0].walk()]
    assert [p for p, _ in tree] == [
        (STEP,), (STEP, "serve/admit"), LAUNCH, (*LAUNCH, "launch/prep"), (*LAUNCH, "launch/h2d"), (*LAUNCH, "launch/dispatch"),
        FETCH, (*FETCH, "fetch/ready"), (*FETCH, "fetch/d2h"), (STEP, "serve/retire"), (STEP, "serve/gauges"),
    ]
    assert dict(tree)[(*LAUNCH, "launch/h2d")] == pytest.approx(0.2e-3)


def test_each_part_of_an_idle_stretch_goes_to_the_innermost_span_over_it():
    events, ops = steps_and_ops(1)
    idle = serve_idle.idle_tree(serve_idle.whole_steps([events]), ops)
    assert idle == {**{p: pytest.approx(v) for p, v in IDLE.items()}, (serve_spans.OUTSIDE,): pytest.approx(1.5e-3)}
    # 0.4 ms from 0.7 ms: 0.25 in prep, 0.05 between prep and h2d (the launch's own), 0.1 in h2d; the device's clock
    # 0.05 ms behind the host's moves that stretch to 0.75-1.15 ms: 0.2 prep, 0.05 own, 0.15 h2d
    shifted = serve_idle.idle_tree(serve_idle.whole_steps([events]), ops, offset=0.05e-3)
    assert shifted[(*LAUNCH, "launch/prep")] == pytest.approx(0.20e-3) and shifted[(*LAUNCH, "launch/h2d")] == pytest.approx(0.15e-3)
    assert sum(shifted.values()) == pytest.approx(sum(idle.values()))


@pytest.mark.parametrize("offset", [0.0, 0.3e-3, -0.4e-3])
def test_the_parts_add_up_to_the_idle_under_each_of_pr27s_spans_and_to_the_devices_idle(offset):
    """Under every ``serve/`` span, its own idle and its children's add up to
    what ``serve_spans.idle_by_span`` gives that span, so the launch's three
    shares never pass it; and all the parts add up to the device's idle."""
    events, ops = steps_and_ops(12)
    idle = serve_idle.idle_tree(serve_idle.whole_steps([events]), ops, offset)
    pr27 = serve_spans.idle_by_span(serve_spans.whole_steps([[e for e in events if e[0].startswith("serve/")]]), ops, offset)
    for name, seconds in pr27.items():
        mine = sum(v for p, v in idle.items() if (p[1:2] or p[:1]) == (name,))  # the path's phase, or the step itself
        assert mine == pytest.approx(seconds), name
    held = sum(v for p, v in idle.items() if p[:2] == LAUNCH and len(p) == 3)
    assert held <= pr27["serve/decode_launch"] + 1e-12
    lo, hi = min(s for _, s, _ in ops), max(s + d for _, s, d in ops)
    busy_s = sum(d for _, _, d in ops)  # none overlaps
    assert sum(idle.values()) == pytest.approx(hi - lo - busy_s)


def run_with(monkeypatch, events, issued=()):
    host = serve_idle.HostSide(serve_idle.whole_steps([events]), list(issued), [])
    monkeypatch.setattr(serve_idle, "host_side", lambda trace_dir: host)
    return types.SimpleNamespace(trace_dir=None)


def test_the_four_shares_through_their_files_and_the_logged_tree(monkeypatch, capsys):
    events, ops = steps_and_ops(12)
    window = PERIOD * 12
    trace = types.SimpleNamespace(devices=[types.SimpleNamespace(ops=ops, modules=[])], window_s=window)
    issued = [PERIOD * i + 1.3e-3 for i in range(12)] + [PERIOD * i + 1.71e-3 for i in range(12)] + [PERIOD * 12 + 1e-3]
    run = run_with(monkeypatch, events, issued)
    expected = {
        "serve_idle_in_prep_share": 0.25e-3, "serve_idle_in_h2d_share": 0.10e-3, "serve_idle_in_dispatch_share": 0.20e-3,
        "serve_idle_in_return_share": 0.18e-3 + 0.13e-3,
    }
    for name in NEW:
        assert serve_idle.read(run, trace, spec(name), "") == pytest.approx(100 * 12 * expected[name] / window), name
    printed = capsys.readouterr().err
    assert printed.count("serve/ launch/ fetch/ tree") == 1  # printed by the first of the four alone
    assert "12 whole steps in a slice of 0.144 s, 11.00 spans a step" in printed
    assert "device clock taken as the host clock" in printed
    outside = 11 * 1.7e-3 + 1.5e-3
    idle_share = 100 * (12 * sum(IDLE.values()) + outside) / window
    rest = 100 * (12 * (0.15 + 0.20 + 0.04 + 0.05 + 0.03) * 1e-3 + outside) / window
    assert (f"device idle prep {100 * 12 * 0.25e-3 / window:.3f}% + h2d {100 * 12 * 0.10e-3 / window:.3f}% + dispatch "
            f"{100 * 12 * 0.20e-3 / window:.3f}% + ready {100 * 12 * 0.18e-3 / window:.3f}% + d2h "
            f"{100 * 12 * 0.13e-3 / window:.3f}% + rest {rest:.3f}% (outside serve/step") in printed
    assert f"= {idle_share:.3f}% of the slice" in printed
    assert f"{100 * 12 * 0.7e-3 / window:.3f}% idle under serve/decode_launch: {100 * 12 * 0.55e-3 / window:.3f}%" in printed
    assert "programs issued inside whole steps: 24, 12 (50.0%) inside a launch/dispatch; serve/decode_launch > launch/dispatch 12, serve/token_fetch 12" in printed
    tree = printed.splitlines()
    prep = next(line for line in tree if line.strip().startswith("launch/prep"))
    assert prep.split()[1:] == ["12", "3.60", "0.300", "3.00", "3.00"]  # count, total, p50 (ms), idle under it, its own


def test_a_program_without_launch_spans_or_a_short_slice_reads_nothing(monkeypatch):
    """The parent writes ``serve/`` spans alone: nothing to read, no error."""
    events, ops = steps_and_ops(12, [p for p in PHASES if p[0].startswith("serve/")])
    trace = types.SimpleNamespace(devices=[types.SimpleNamespace(ops=ops, modules=[])], window_s=PERIOD * 12)
    with_spans, _ = steps_and_ops(9)
    for name in NEW:
        assert serve_idle.read(run_with(monkeypatch, events), trace, spec(name), "") is None
        assert serve_idle.read(run_with(monkeypatch, with_spans), trace, spec(name), "") is None
        assert serve_idle.read(run_with(monkeypatch, []), trace, spec(name), "") is None


def test_no_trace_under_the_directory_reads_as_nothing(tmp_path):
    assert serve_idle.host_side(tmp_path).steps == []
    assert serve_idle.read(types.SimpleNamespace(trace_dir=tmp_path), None, spec(NEW[0]), "") is None


def test_the_four_close_per_layer_and_are_read_in_the_four_serving_cells():
    m = Manifest()
    names = list(m.per_layer)
    # appended together and in this order; a later cell's metrics are appended after them, so compared by name
    assert names[names.index(NEW[0]) :][:4] == list(NEW)
    serving = ["lm-serve-chat", "keye-serve-long", "lm-serve-long", "mellum-serve-mixed"]
    for name in NEW:
        assert [c for c in m.cells if name in m.cell_per_layer(c)] == serving
        assert m.metric_file(name)["reader"] == "serve_idle" and m.per_layer[name]["source"] == "program_span"
        assert m.per_layer[name]["layer"] == m.per_layer["serve_idle_in_launch_share"]["layer"]
