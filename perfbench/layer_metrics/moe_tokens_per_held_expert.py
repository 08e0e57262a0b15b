"""Tokens a held expert sees in a decode step: the router's
assignments that fell on experts held on this chip, per expert layer
computed, over the experts held. From the program's counters
`defer_moe_assignments_held_total` and `defer_moe_layer_steps_total`
(`phase="decode"`, live slots' rows only) between the window's edges.
Uniform routing gives live slots x experts per token / published
experts: 2.0 at 32 slots, top-8 of 128. None where the program has no
such counters or no decode step ran."""

from perfbench import moe_counters


def read(run):
    assigned = moe_counters.per_layer_step(run, "assignments_held")
    return None if assigned is None else assigned / run.model["num_experts"]
