"""Device time of one execution of the compiled train step, from the
trace: the step program's "XLA Modules" seconds / its executions."""


def read(obs):
    trace = obs.get("trace")
    program = (trace or {}).get("programs", {}).get(obs.get("step_program"))
    if not program or not program["executions"]:
        return None
    return 1e3 * program["device_s"] / program["executions"]
