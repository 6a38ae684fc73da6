import json

from benchmark import schedule
from benchmark.manifest import ROOT

TRAFFIC = json.loads((ROOT / "benchmark/traffic/chat-steady-2p4.json").read_text())


def test_schedule_is_byte_identical_for_its_seed_and_blind_to_the_run_seed():
    a, b = schedule.build(TRAFFIC, 51), schedule.build(dict(TRAFFIC), 51)
    assert schedule.dumps(a) == schedule.dumps(b)
    other = schedule.build({**TRAFFIC, "schedule_seed": TRAFFIC["schedule_seed"] + 1}, 51)
    assert schedule.dumps(other) != schedule.dumps(a)
    # --seed reaches the token ids only: same lengths and times, other ids
    r = a[3]
    one = schedule.prompt_ids(1, 3, r["prompt_len"], 32000)
    two = schedule.prompt_ids(2**31 + 5, 3, r["prompt_len"], 32000)
    assert len(one) == len(two) == r["prompt_len"] and (one != two).any()
    assert (schedule.prompt_ids(1, 3, r["prompt_len"], 32000) == one).all()


def test_a_shorter_window_is_a_prefix_and_the_rate_is_the_nominal_one():
    full, short = schedule.build(TRAFFIC, 51), schedule.build(TRAFFIC, 10)
    assert short == full[: len(short)] and 0 < len(short) < len(full)
    horizon = TRAFFIC["lead_seconds"] + 51
    assert abs(len(full) / horizon - TRAFFIC["rate_per_s"]) < 0.05
    assert all(a["due"] < b["due"] for a, b in zip(full, full[1:]))
    p, o = TRAFFIC["prompt_tokens"], TRAFFIC["output_tokens"]
    assert all(p["min"] <= r["prompt_len"] <= p["max"] and o["min"] <= r["new_tokens"] <= o["max"] for r in full)
    assert max(r["prompt_len"] for r in full) == p["max"]  # the longest prompt the engine admits is in the mix
