"""Time the collective operations take per train step, from the trace of
the first device: each collective's span (a ``-start`` to the end of its
``-done`` where it is asynchronous) / executions of the step program."""


def read(obs):
    trace = obs.get("trace")
    program = (trace or {}).get("programs", {}).get(obs.get("step_program"))
    if not program or not program["executions"] or not trace["collective_s"]:
        return None
    return 1e3 * trace["collective_s"] / program["executions"]
