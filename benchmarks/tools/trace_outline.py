"""Look at a trace by hand: planes, lines, how many events each holds and
the names that take most time.  With ``--slice-ms A B OUT`` it also
writes the events that overlap [A, B) ms of the trace, clipped to it (the
plain form ``trace/reduce.py`` works on), to OUT, which is how the
recorded fixture beside the reduction was cut.

    python3 benchmarks/tools/trace_outline.py <file.xplane.pb> [--slice-ms A B OUT]
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness.loading import load_module  # noqa: E402


def main(argv) -> int:
    reducer = load_module("trace", "reduce")
    if "--count" in argv:
        # every plane and line with its number of events, holding none
        from jax.profiler import ProfileData

        for plane in ProfileData.from_file(argv[0]).planes:
            print(f"PLANE {plane.name}")
            for line in plane.lines:
                n, total, sample = 0, 0.0, []
                for e in line.events:
                    n += 1
                    total += e.duration_ns
                    if n <= 3:
                        sample.append(e.name[:60])
                print(f"  LINE {line.name!r}: {n} events, {total / 1e6:.3f} ms; {sample}")
        return 0
    trace = reducer.load(argv[0])
    starts = [
        e[1] for p in trace["planes"] for ln in p["lines"] for e in ln["events"]
    ]
    t0 = min(starts) if starts else 0.0
    for plane in trace["planes"]:
        print(f"PLANE {plane['name']}")
        for line in plane["lines"]:
            events = line["events"]
            total = {}
            for name, _, dur in events:
                total[name] = total.get(name, 0.0) + dur
            top = sorted(total.items(), key=lambda kv: -kv[1])[:8]
            first = min((e[1] for e in events), default=t0) - t0
            print(f"  LINE {line['name']!r}: {len(events)} events, first at "
                  f"{first / 1e6:.3f} ms")
            for name, dur in top:
                print(f"      {dur / 1e6:10.3f} ms  {name[:100]}")
    print(json.dumps(reducer.reduce(trace), indent=1)[:4000])
    if "--slice-ms" in argv:
        i = argv.index("--slice-ms")
        lo, hi = t0 + float(argv[i + 1]) * 1e6, t0 + float(argv[i + 2]) * 1e6
        cut = {"planes": []}
        for plane in trace["planes"]:
            lines = []
            for line in plane["lines"]:
                events = [
                    [n, max(s, lo) - lo, min(s + d, hi) - max(s, lo)]
                    for n, s, d in line["events"] if s < hi and s + d > lo
                ]
                if events:
                    lines.append({"name": line["name"], "events": events})
            if lines:
                cut["planes"].append({"name": plane["name"], "lines": lines})
        with open(argv[i + 3], "w") as f:
            json.dump(cut, f, separators=(",", ":"))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
