"""What the program's own span tracer costs while it records.  One run of
a cell exactly as ``run.py --trace 0`` makes it, with the tracer recording
into its buffer from before the driver starts to the result line
(``--record 1``) or idle (``--record 0``); the cost is the difference of
the end-to-end medians over runs of both kinds on the same seeds.  The
last line of standard output is ``run.py``'s result line; how many events
the tracer held and dropped goes to standard error.

    python3 benchmarks/tools/tracing_cost.py --record <0|1> --workload <cell> --seed <n> --seconds <s>
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run as run_module  # noqa: E402


def main(argv) -> int:
    at = argv.index("--record")
    record, rest = argv[at + 1] == "1", argv[:at] + argv[at + 2:]
    from znicz_tpu.observability import get_tracer

    tracer = get_tracer()
    if record:
        tracer.start()
    rc = run_module.main(rest + ["--trace", "0"])
    held = len(tracer.stop()) if record else 0
    print(
        f"tracer recording: {record}; events held {held}, "
        f"dropped {tracer.dropped}", file=sys.stderr, flush=True,
    )
    return rc


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
