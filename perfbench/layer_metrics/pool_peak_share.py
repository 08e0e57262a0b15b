"""Most blocks of the KV pool held at once, over the blocks it has
(block 0 is the server's own)."""


def read(run):
    return 100.0 * run.blocks_peak / (run.server_args["num_blocks"] - 1)
