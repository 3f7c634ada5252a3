"""The decode program's share of its memory roofline: bytes a decode step
must read (the weights of the experts that received a token, from the
program's counter; every other weight once; the latent rows gathered)
over the chip's HBM bandwidth, against the device time of whole
executions of ``jit__paged_decode_chunk`` in the trace and the steps they
ran."""

from harness import axk1_readers as _shared, axk1_work


def read(obs):
    means, traced = _shared.decode_means(obs), _shared.traced_decode(obs)
    if means is None or traced is None or not obs.get("peaks"):
        return None
    step_bytes = axk1_work.decode_step_bytes(
        means["cfg"], means["experts_hit_per_layer"],
        means["rows_gathered_per_step"],
    )
    least_s = step_bytes / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s * traced["steps"] / traced["device_s"]
