"""Process start to the window opening: imports, weights, pool,
the correctness check, warm-up of the cell's shapes, the standing
requests seated. Host clock."""


def read(run):
    return run.setup_s
