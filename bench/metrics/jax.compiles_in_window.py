"""``jax.compiles_in_window``: backend compiles and persistent-cache loads
that happened inside the measured window, counted by the harness's
``jax.monitoring`` listener on ``/jax/core/compile/backend_compile_duration``.
Every shape is warmed up before the window, so anything here is a program
that is traced and compiled again on every step."""


def read(ctx):
    return float(ctx["compiles_in_window"])
