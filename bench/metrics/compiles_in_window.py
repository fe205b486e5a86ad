"""Backend compiles (jax.monitoring) inside the measured window; every
program the traffic opens is compiled in set-up, so this should be 0."""


def read(run):
    return run.compiles_in_window
