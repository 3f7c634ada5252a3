"""The general traffic generator: the schedule of a run does not change
with the seed, only the token ids do."""

import pytest

import toy  # noqa: F401
from harness import traffic

MIX = {
    "arrivals": {"process": "poisson", "rate_per_s": 10.0},
    "prompt_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.7, "min": 32, "max": 768},
    "answer_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.5, "min": 16, "max": 256},
    "shared_prefix_tokens": 0, "ramp_s": 6.0, "deadline_s": 30.0, "shape_seed": 0,
}


def _sizes(plan, counted):
    chosen = [p for p in plan if p.counted == counted]
    return (
        sorted(len(p.prompt) for p in chosen),
        sorted(p.max_new_tokens for p in chosen),
        sorted(round(b.due_s - a.due_s, 9) for a, b in zip(chosen, chosen[1:])),
    )


@pytest.mark.parametrize("process,cv", [("poisson", None), ("gamma", 3.0)])
def test_every_seed_gets_the_same_schedule_and_other_tokens(process, cv):
    mix = dict(MIX, arrivals={"process": process, "rate_per_s": 10.0, "cv": cv})
    a = traffic.schedule(mix, 3_000_000_001, 20.0, 50257, 1024, pad_to=32)
    b = traffic.schedule(mix, 7, 20.0, 50257, 1024, pad_to=32)
    assert len([p for p in a if p.counted]) == 200
    assert len([p for p in a if not p.counted]) == 60
    for counted in (True, False):
        assert _sizes(a, counted) == _sizes(b, counted)
    assert [(p.due_s, len(p.prompt), p.max_new_tokens) for p in a] == [
        (p.due_s, len(p.prompt), p.max_new_tokens) for p in b
    ]
    # the lengths are not in quantile order: the fixed stream shuffled them
    assert [len(p.prompt) for p in a if p.counted] != sorted(
        len(p.prompt) for p in a if p.counted
    )
    assert all(0.0 <= p.due_s < 20.0 for p in a if p.counted)
    assert all(-6.0 <= p.due_s < 0.0 for p in a if not p.counted)
    assert a[0].prompt != b[0].prompt


def test_same_seed_same_requests():
    a = traffic.schedule(MIX, 11, 5.0, 50257, 1024)
    b = traffic.schedule(MIX, 11, 5.0, 50257, 1024)
    assert [(p.due_s, p.prompt, p.max_new_tokens) for p in a] == [
        (p.due_s, p.prompt, p.max_new_tokens) for p in b
    ]


def test_shared_prefix_and_unknown_parameter():
    plan = traffic.schedule(dict(MIX, shared_prefix_tokens=16), 3, 5.0, 50257, 1024)
    assert len({tuple(p.prompt[:16]) for p in plan}) == 1
    with pytest.raises(ValueError, match="does not know"):
        traffic.schedule(dict(MIX, burst_size=4), 3, 5.0, 50257, 1024)
    with pytest.raises(ValueError, match="exceed"):
        traffic.schedule(MIX, 3, 5.0, 50257, 512)
