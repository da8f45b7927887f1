"""Device time of one fused step, from the profiler slice: summed
duration of the step's programs on the ``XLA Modules`` line over their
count."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["steps"]:
        return None
    return 1e3 * tr["step_seconds_total"] / tr["steps"]
