"""The one general generator of request traffic.  A mix is a data file of
parameters; this turns it, a seed and a window length into a schedule.

Every seed gets the SAME schedule: lengths are the quantiles of the mix's
distributions, gaps between arrivals are drawn from a stream fixed in the
mix (``shape_seed``) and scaled to fill the window exactly, both are put
in an order that the same stream fixes, and ``--seed`` draws only the
token ids (and the weights, elsewhere).  Permuting the order by the seed
was tried first (my chip runs, PR 23): with some forty requests in a
window, which long prompts happen to arrive together moved the 95th
percentile of time to first token by +-20 % from seed to seed while two
runs of one seed agreed to a few percent, so the seed was changing the
work.

Parameters (a key not listed is an error):

- ``arrivals``: ``{"process": "poisson" | "gamma", "rate_per_s", "cv"}``
  (``cv`` for gamma only: 1 is Poisson, 3 is bursty);
- ``prompt_tokens``, ``answer_tokens``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}`` or ``{"dist": "fixed", "value"}``;
- ``shared_prefix_tokens``: leading tokens every prompt shares (0: none);
- ``ramp_s``: seconds of the same traffic sent before the window opens,
  so the window sees a server in steady state; not counted;
- ``deadline_s``: a request still unfinished that long after it was due
  has failed; ``shape_seed``: see above.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List

import numpy as np

KNOWN = {
    "arrivals", "prompt_tokens", "answer_tokens", "shared_prefix_tokens",
    "ramp_s", "deadline_s", "shape_seed",
}


@dataclasses.dataclass
class Planned:
    due_s: float  # relative to the window's opening; negative in the ramp
    prompt: List[int]
    max_new_tokens: int
    counted: bool


def _lengths(spec: dict, n: int) -> np.ndarray:
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    normal = NormalDist()
    q = np.array([normal.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * q)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def _gaps(arrivals: dict, n: int, span_s: float, rng) -> np.ndarray:
    if arrivals["process"] == "poisson":
        gaps = rng.exponential(1.0, n)
    elif arrivals["process"] == "gamma":
        shape = 1.0 / float(arrivals["cv"]) ** 2
        gaps = rng.gamma(shape, 1.0 / shape, n)
    else:
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    return gaps * (span_s / gaps.sum())


def _phase(mix, n, span_s, start_s, shape_rng, seed_rng, vocab, prefix, counted):
    if n == 0:
        return []
    gaps = _gaps(mix["arrivals"], n, span_s, shape_rng)
    # an arrival sits in the middle of its gap, so the first is not at 0
    due = start_s + np.cumsum(gaps) - gaps / 2
    prompts = shape_rng.permutation(_lengths(mix["prompt_tokens"], n))
    answers = shape_rng.permutation(_lengths(mix["answer_tokens"], n))
    out = []
    for t, p_len, a_len in zip(due, prompts, answers):
        body = seed_rng.integers(1, vocab, max(int(p_len) - len(prefix), 0))
        out.append(
            Planned(
                float(t), list(prefix) + body.tolist(), int(a_len), counted
            )
        )
    return out


def schedule(mix: dict, seed: int, seconds: float, vocab: int,
             max_total: int, pad_to: int = 1) -> List[Planned]:
    """The ramp's requests (``counted`` false) and the window's, by due
    time.  ``max_total`` caps prompt + answer (the server's ``max_seq``),
    the prompt rounded up to a multiple of ``pad_to`` as a paged server
    rounds it."""
    unknown = set(mix) - KNOWN
    if unknown:
        raise ValueError(f"the traffic generator does not know {sorted(unknown)}")
    rate = float(mix["arrivals"]["rate_per_s"])
    ramp_s = float(mix.get("ramp_s", 0.0))
    shape_rng = np.random.default_rng(int(mix.get("shape_seed", 0)))
    seed_rng = np.random.default_rng(int(seed))
    prefix = seed_rng.integers(
        1, vocab, int(mix.get("shared_prefix_tokens", 0))
    ).tolist()
    plan = _phase(
        mix, int(round(rate * ramp_s)), ramp_s, -ramp_s, shape_rng, seed_rng,
        vocab, prefix, False,
    ) + _phase(
        mix, int(round(rate * seconds)), seconds, 0.0, shape_rng, seed_rng,
        vocab, prefix, True,
    )
    for p in plan:
        padded = -(-len(p.prompt) // pad_to) * pad_to
        if padded + p.max_new_tokens > max_total:
            raise ValueError(
                f"prompt {len(p.prompt)} + answer {p.max_new_tokens} tokens "
                f"exceed the server's {max_total}"
            )
    return plan
