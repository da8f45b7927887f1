"""Seconds the backend spent on the programs asked of it inside the
window: rise of ``jax_backend_compile_seconds``' sum (obs/runtime.py: a
``jax.monitoring`` listener inside the program; XLA's compile or the
disk cache's read, the event spans both). ``compiles_in_window`` counts
the same events from the harness and cannot weigh them. A program
without the histogram gives None."""


def read(ctx):
    reg = ctx["registry"]
    if "jax_backend_compile_seconds" not in reg:
        return None
    n = reg.get("jax_backend_compile_seconds_count", 0.0)
    print(f"[layer] compiles: {n:g} by the program's listener "
          f"({ctx['compiles']:g} by the harness's), "
          f"{reg['jax_backend_compile_seconds']:.3f} s", flush=True)
    return reg["jax_backend_compile_seconds"]
