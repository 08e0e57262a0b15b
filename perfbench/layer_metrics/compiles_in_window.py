"""Programs built inside the window (jax.monitoring: every lowering,
whether the compile cache had the program or not). Must read 0."""


def read(run):
    return run.builds_close["lowered"] - run.builds_open["lowered"]
