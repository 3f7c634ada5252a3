"""The decode program's share of its memory roofline in the traced seconds:
the bytes a decode step must read (every non-expert weight once, the
weights of the experts that received a token from the idle-experts counter,
the cached rows each kind's layers read x 4,096 B;
``harness/laguna_work.decode_step_bytes``) over the chip's HBM bandwidth,
against the device time of whole executions of ``jit__paged_decode_chunk``
in the trace and the steps they ran."""

from harness import laguna_readers as _shared, laguna_work


def read(obs):
    means, traced = _shared.decode_means(obs), _shared.traced_decode(obs)
    if means is None or traced is None or not obs.get("peaks"):
        return None
    step_bytes = laguna_work.decode_step_bytes(
        means["cfg"], means["experts_hit_per_layer"], means["rows_read_per_step"]
    )
    least_s = step_bytes / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s * traced["steps"] / traced["device_s"]
