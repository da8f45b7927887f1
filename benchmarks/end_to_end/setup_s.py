"""Everything before the measured window, from the start of the process:
imports, device, server, registration, population, agents, warm-up of
every shape, and the unmeasured seconds of the cell's own traffic."""


def read(ctx):
    return ctx["setup_s"]
