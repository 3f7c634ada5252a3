"""95th percentile, over the window's requests, of the first streamed
token's arrival minus the time the request was DUE (the client's clock,
HTTP included).  Recorded here and not judged end to end: see PERF.md
section 2."""


def read(obs):
    return (obs.get("client") or {}).get("ttft_p95_ms")
