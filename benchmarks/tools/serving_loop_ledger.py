"""Where a turn of the serving thread went, stage by stage.  One run of a
serving cell exactly as ``run.py`` makes it (``--trace 0`` or ``1``); the
last line of standard output is ``run.py``'s result line, and the window's
``znicz_serve_loop_seconds{stage}`` goes to standard error as a table: laps,
seconds, ms a decode (or verify) chunk and ms a turn, then the turns, the
decode periods and the prefill chunks between two decode chunks.

    python3 benchmarks/tools/serving_loop_ledger.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run as run_module  # noqa: E402
from harness import registry, serving_loop  # noqa: E402


class _Kept(registry.Delta):
    """Every delta a driver takes is kept: the window's is the one that
    holds the most turns (a driver may take a shorter one beside it for
    its traced seconds)."""

    taken: list = []

    def __init__(self, before, after):
        super().__init__(before, after)
        _Kept.taken.append(self)


def _turns(delta) -> int:
    return (delta.hist(serving_loop.TURNS) or {"count": 0})["count"]


def table(delta) -> str:
    obs = {"registry": delta}
    chunks, turns = serving_loop.decode_chunks(obs), _turns(delta)
    lines = [f"{'stage':28s} {'laps':>8s} {'seconds':>10s} {'ms/chunk':>9s} {'ms/turn':>8s}"]
    stages = serving_loop.FRONTDOOR + serving_loop.WAITS + serving_loop.ENGINE_HOST
    for stage in sorted(stages):
        lap = delta.hist(serving_loop.STAGES, stage=stage)
        if lap is None:
            continue
        lines.append(
            f"{stage:28s} {lap['count']:8d} {lap['sum']:10.4f} "
            f"{1e3 * lap['sum'] / max(chunks, 1):9.3f} "
            f"{1e3 * lap['sum'] / max(turns, 1):8.3f}"
        )
    for name in (
        serving_loop.TURNS, "znicz_serve_decode_period_seconds",
        "znicz_serve_prefill_chunks_between_decodes",
    ):
        h = delta.hist(name)
        if h is not None:
            lines.append(
                f"{name}: count {h['count']}, sum {h['sum']:.4f}, "
                f"mean {h['sum'] / h['count']:.5f}"
            )
    lines.append(f"decode and verify chunks: {chunks}; turns with work: {turns}")
    return "\n".join(lines)


def main(argv) -> int:
    registry.Delta = _Kept
    rc = run_module.main(argv)
    if _Kept.taken:
        print(table(max(_Kept.taken, key=_turns)), file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
