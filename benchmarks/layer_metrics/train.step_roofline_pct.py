"""The train step's share of its roofline.  The bound is compute: 3 x
the forward model FLOPs (forward, input gradient, weight gradient) x the
per-chip batch at the chip's bf16 peak, over the step's device time."""


def read(obs):
    trace = obs.get("trace")
    program = (trace or {}).get("programs", {}).get(obs.get("step_program"))
    if not program or not program["device_s"] or not obs.get("peaks"):
        return None
    least_s = obs["step_flops_per_chip"] / obs["peaks"]["bf16_flops"]
    return 100.0 * least_s * program["executions"] / program["device_s"]
