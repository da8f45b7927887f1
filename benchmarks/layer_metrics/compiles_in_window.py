"""Backend compiles inside the measured window (jax.monitoring's
``/jax/core/compile/backend_compile_duration`` events, counted as
chip_smoke._CompileCounter does). Every shape is warmed in set-up: this
should read 0, and each one is a stall of seconds in the tail."""


def read(ctx):
    return float(ctx["compiles"])
