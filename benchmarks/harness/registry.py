"""Reading the program's metrics registry as plain sums and counts, and
the difference between two readings (the window's own share)."""

from __future__ import annotations

from typing import Dict, Tuple

Key = Tuple[str, Tuple[Tuple[str, str], ...]]


def read() -> Dict[Key, dict]:
    from znicz_tpu.observability import get_registry

    out: Dict[Key, dict] = {}
    for name, metric in get_registry().snapshot().items():
        for series in metric["series"]:
            key = (name, tuple(sorted(series["labels"].items())))
            if "count" in series:
                out[key] = {"sum": series["sum"], "count": series["count"]}
            else:
                out[key] = {"value": series["value"]}
    return out


class Delta:
    """``after - before`` for every series; ``hist``/``value`` return the
    sum over the series whose labels include the ones asked for, or None
    where nothing was observed."""

    def __init__(self, before: Dict[Key, dict], after: Dict[Key, dict]):
        self._d: Dict[Key, dict] = {}
        for key, now in after.items():
            was = before.get(key, {})
            self._d[key] = {k: v - was.get(k, 0) for k, v in now.items()}

    def _matching(self, name: str, labels: dict):
        want = {(k, str(v)) for k, v in labels.items()}
        return [
            d for (n, lab), d in self._d.items()
            if n == name and want <= {(k, str(v)) for k, v in lab}
        ]

    def hist(self, name: str, **labels):
        found = [d for d in self._matching(name, labels) if "count" in d]
        count = sum(d["count"] for d in found)
        if not count:
            return None
        return {"sum": sum(d["sum"] for d in found), "count": count}

    def value(self, name: str, **labels):
        found = [d for d in self._matching(name, labels) if "value" in d]
        if not found:
            return None
        return sum(d["value"] for d in found)

    def phases(self, name: str) -> Dict[str, dict]:
        """{phase label: {sum, count}} of one phase histogram."""
        out = {}
        for (n, lab), d in self._d.items():
            if n == name and "count" in d and d["count"]:
                out[dict(lab).get("phase", "")] = d
        return out
