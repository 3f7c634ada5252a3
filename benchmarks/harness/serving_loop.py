"""What the readers of the serving thread's stage clock share (PR 38):
the names of its stages by what the thread does in them, and their
seconds over the window.

``znicz_serve_loop_seconds{stage}`` tiles one turn of the front door and
the engine; ``znicz_serve_loop_iteration_seconds`` holds each turn that
had work.  A program without them (a parent commit) gives every reader
None."""

STAGES = "znicz_serve_loop_seconds"
TURNS = "znicz_serve_loop_iteration_seconds"

_CHUNK_HOST = ("grow", "prepare", "dispatch", "fetch", "emit")
# where the thread blocks on the device, by design
CHUNK_WAITS = ("serve/decode/wait", "serve/verify/wait")
WAITS = ("serve/prefill/wait",) + CHUNK_WAITS
# every other stage of the engine: the host's own work
ENGINE_HOST = (
    "serve/schedule", "serve/prefill/host", "serve/verify/draft",
    *(f"serve/decode/{part}" for part in _CHUNK_HOST),
    *(f"serve/verify/{part}" for part in _CHUNK_HOST),
)
FRONTDOOR = (
    "frontdoor/control", "frontdoor/pump", "frontdoor/stream",
    "frontdoor/housekeeping",
)


def seconds(obs, stages):
    """Seconds the window spent in ``stages``; None where none of them
    was observed."""
    found = [obs["registry"].hist(STAGES, stage=s) for s in stages]
    found = [f for f in found if f]
    return sum(f["sum"] for f in found) if found else None


def decode_chunks(obs):
    """Decode and verify chunks of the window: each waits once."""
    found = [obs["registry"].hist(STAGES, stage=s) for s in CHUNK_WAITS]
    return sum(f["count"] for f in found if f)


def ms_per_decode_chunk(obs, stages):
    spent, chunks = seconds(obs, stages), decode_chunks(obs)
    if spent is None or not chunks:
        return None
    return 1e3 * spent / chunks
