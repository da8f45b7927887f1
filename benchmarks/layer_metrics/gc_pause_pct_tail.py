"""Share of the window the server's process spent inside Python's
cyclic collector (gc.callbacks, start to stop), in percent. A full
collection stops every thread of the interpreter at once: below the knee
the operations that fall due inside one make the tail beyond the 90th
percentile."""


def read(ctx):
    if ctx.get("gc") is None or ctx["seconds"] <= 0:
        return None
    return 100.0 * ctx["gc"] / ctx["seconds"]
