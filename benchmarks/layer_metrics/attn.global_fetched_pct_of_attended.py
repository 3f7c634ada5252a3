"""The share of a global layer's per-row cache traffic that a decode step
still fetches, in the traced seconds: the cached rows a global layer
FETCHED (``znicz_serve_decode_cached_rows_total{kind=global}``: blocks that
the live rows of a tile open with in common count once) over the rows its
queries MET (``znicz_serve_decode_attended_rows_total{kind=global}``, a row
counted for each query), x 100.  100 where every row reads its whole table
for itself; None on a program that has no such counter (a parent commit)."""

from harness import laguna_readers as _shared


def read(obs):
    reg = obs.get("traced_registry")
    if reg is None or not _shared.is_ours(obs.get("config")):
        return None
    fetched = reg.value("znicz_serve_decode_cached_rows_total", kind="global")
    attended = reg.value("znicz_serve_decode_attended_rows_total", kind="global")
    if fetched is None or not attended:
        return None
    return 100.0 * fetched / attended
