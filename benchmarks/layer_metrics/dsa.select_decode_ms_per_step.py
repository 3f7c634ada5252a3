"""Device milliseconds a decode step spends choosing the keys its rows keep,
all layers: the operations the program marks ``dsa_select`` inside
``jit__paged_decode_chunk`` (32 counts over every slot's scores), over the
whole executions of the decode program in the trace.  No roofline: the
selection moves no model bytes."""

from harness import keye_readers as _shared


def read(obs):
    if _shared.decode_means(obs) is None:
        return None
    found = _shared.scope_seconds(obs, "dsa_select")
    if found is None:
        return None
    seconds, steps = found
    return 1e3 * seconds / steps
