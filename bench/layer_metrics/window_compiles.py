"""Device (compile): backend compiles JAX reported
(``/jax/core/compile/backend_compile_duration`` events of
``jax.monitoring``) between the first submission and the last final.
Set-up warms every shape the cell's traffic forms, so this reads 0."""


def read(run):
    return float(run.window.compiles)
