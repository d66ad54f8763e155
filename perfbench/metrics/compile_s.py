"""compile_s: backend compile seconds during set-up (layer: compile).

Read from the program's compile counter (`telemetry.compile_watch`, the
`jax.monitoring` backend-compile events); a load from the persistent
compile cache counts as the short compile it is.  Moves `setup_s`."""


def read(run):
    return run.compile_s
