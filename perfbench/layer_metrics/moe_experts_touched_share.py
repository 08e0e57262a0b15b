"""The share of the held experts that some live token chose, mean over
the expert layers of the window's decode steps: the part of the held
expert weights a step has to read. From the program's counters
`defer_moe_experts_touched_total` and `defer_moe_layer_steps_total`
(`phase="decode"`) between the window's edges. Uniform routing of 32
slots x top-8 over 128 experts gives 1 - (1 - 1/128)^256 = 87%. None
where the program has no such counters or no decode step ran."""

from perfbench import moe_counters


def read(run):
    touched = moe_counters.per_layer_step(run, "experts_touched")
    return None if touched is None else 100.0 * touched / run.model["num_experts"]
