"""Time the training loop waited at an epoch's edges, spread over the
window's steps: the first batch of each epoch (the producer starts with
the epoch, so that fetch overlaps no step) and the end-of-epoch sentinel:
``znicz_prefetch_wait_seconds{at=first}`` + ``{at=end}`` sums / steps."""


def read(obs):
    edges = [
        obs["registry"].hist("znicz_prefetch_wait_seconds", at=at)
        for at in ("first", "end")
    ]
    if all(e is None for e in edges) or not obs.get("steps"):
        return None
    return 1e3 * sum(e["sum"] for e in edges if e) / obs["steps"]
