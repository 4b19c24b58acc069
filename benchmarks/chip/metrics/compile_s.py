"""Backend compile and compile-cache load seconds during set-up, from
``jax.monitoring`` duration events."""


def read(run):
    return run.compile_s
