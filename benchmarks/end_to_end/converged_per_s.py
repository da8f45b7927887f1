"""Operations whose convergence was SEEN inside the window, over the
window's length: the rate the system completes at, whatever was due."""


def read(ctx):
    a, b = ctx["window"]
    n = sum(1 for o in ctx["all_ops"]
            if o.get("seen") is not None and a <= o["seen"] < b)
    return n / ctx["seconds"] if n else None
