"""How the cache manager's live bytes divide between its two kinds of
state: the recurrent layers' states of the live slots over those plus
the K/V bytes of the blocks in use, mean of the window's two edges,
in per cent. From the program's gauges as `Run.registry_open` and
`registry_close` hold them: `defer_linear_state_pool_bytes` (the state
pools as allocated, all slots) x `defer_linear_state_slots_live` over
the server's `max_batch`, and `defer_pool_blocks_used` x the bytes of
one block (`Run.pool_bytes` over the pool's blocks). None where the
program has no such gauge (a program without recurrent layers'
pools) or holds no state."""

STATE_BYTES = 'defer_linear_state_pool_bytes{server="paged"}'
STATE_LIVE = 'defer_linear_state_slots_live{server="paged"}'
BLOCKS_USED = 'defer_pool_blocks_used{server="paged"}'


def share(registry: dict, run):
    if not registry.get(STATE_BYTES) or STATE_LIVE not in registry:
        return None
    args = run.server_args
    state = registry[STATE_BYTES] * registry[STATE_LIVE] / args["max_batch"]
    kv = registry.get(BLOCKS_USED, 0) * run.pool_bytes / args["num_blocks"]
    return 100.0 * state / (state + kv) if state + kv else None


def read(run):
    edges = [share(r, run) for r in (run.registry_open, run.registry_close)]
    if None in edges:
        return None
    return sum(edges) / len(edges)
