"""Device time of one execution of the decode-chunk program, from the
trace: its "XLA Modules" seconds / executions, over every window rung."""


def read(obs):
    trace = obs.get("trace")
    program = (trace or {}).get("programs", {}).get(obs.get("decode_program"))
    if not program or not program["executions"]:
        return None
    return 1e3 * program["device_s"] / program["executions"]
