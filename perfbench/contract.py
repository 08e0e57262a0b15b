"""What a configuration file owes its source, as a function.

A configuration's file states `published`: the source's own value of
every key the file takes from it. `check_config` holds the file to
that, whatever the architecture: the contract test calls it for every
entry of `configs`, so a second family is new files and entries alone.
"""

from __future__ import annotations

import math
import re

# Keys that name a width: never cut, so never in `reduced`. A window is
# one (`sliding_window`, `window_size`); a key that lays windows out
# over the layers (`max_window_layers`, `sliding_window_pattern`) is not.
WIDTH = re.compile(
    r"(hidden_size|intermediate|latent|state_size|head_dim|_dim$|_rank$"
    r"|experts_per_tok|window(_size)?$|_width$)"
)
# Keys that count the routed experts of a layer.
EXPERT_COUNT = re.compile(r"^(moe_)?n(um)?_(routed_|local_|primary_)?experts$")
# Keys whose whole number says after how many layers the pattern repeats.
PERIOD = re.compile(r"_(period|interval)$")
MIN_EXPERTS = 8
MIN_VOCAB_SHARE = 1 / 8


def list_period(kinds: list) -> int:
    """The least p for which the list repeats itself every p entries."""
    return next(
        p for p in range(1, len(kinds) + 1)
        if all(a == b for a, b in zip(kinds, kinds[p:]))
    )


def layer_period(published: dict) -> int:
    """After how many layers the published pattern repeats: the least
    common multiple of the period of `layer_types` and of every whole
    number under a key that names a period; 1 where none is stated."""
    periods = [
        v for k, v in published.items()
        if PERIOD.search(k) and isinstance(v, int) and v > 0
    ]
    kinds = published.get("layer_types")
    if isinstance(kinds, list) and kinds:
        periods.append(list_period(kinds))
    return math.lcm(*periods) if periods else 1


def _changed_widths(was, now, path: str) -> list[str]:
    """Width keys inside a nested group that differ from the source."""
    out = []
    for k, v in was.items():
        here = f"{path}.{k}"
        if isinstance(v, dict) and isinstance(now.get(k), dict):
            out += _changed_widths(v, now[k], here)
        elif WIDTH.search(k) and now.get(k) != v:
            out.append(here)
    return out


def check_config(entry: dict, cfg: dict) -> list[str]:
    """The faults of one configuration: `entry` is its entry of
    `configs` in BENCHMARK.json, `cfg` its file. Empty where it keeps
    to its source."""
    faults = []
    published = cfg.get("published")
    if not isinstance(published, dict) or not published:
        return ["the file states no `published` values of its source"]
    reduced = entry["reduced"]

    for k, was in published.items():
        if k in reduced:
            if cfg.get(k) == was:
                faults.append(f"{k} is in `reduced` and equals the published {was!r}")
            elif isinstance(was, dict) and isinstance(cfg.get(k), dict):
                faults += [
                    f"{w} is a width inside the reduced group {k} and differs from the source"
                    for w in _changed_widths(was, cfg[k], k)
                ]
        elif k not in cfg:
            faults.append(f"{k} is published, not in the file and not in `reduced`")
        elif cfg[k] != was:
            faults.append(
                f"{k} is {cfg[k]!r}, published {was!r}, and is not in `reduced`"
            )
    for k in reduced:
        if k not in published:
            faults.append(f"{k} is in `reduced` with no published value to differ from")
        if WIDTH.search(k):
            faults.append(f"{k} is a width: it may not be in `reduced`")
    for k in cfg:
        if WIDTH.search(k) and k not in published:
            faults.append(f"{k} is a width the file states without its published value")

    # The floors of the model-configs guide, section 4.
    cut = [k for k in reduced if k in cfg and k in published]
    for k in cut:
        if EXPERT_COUNT.search(k) and cfg[k] < MIN_EXPERTS:
            faults.append(f"{k} is {cfg[k]}: fewer than {MIN_EXPERTS} routed experts")
    if "vocab_size" in cut and cfg["vocab_size"] < published["vocab_size"] * MIN_VOCAB_SHARE:
        faults.append(
            f"vocab_size is {cfg['vocab_size']}: under an eighth of the "
            f"published {published['vocab_size']}"
        )
    period = layer_period(published)
    if "num_hidden_layers" in cut and cfg["num_hidden_layers"] % period:
        faults.append(
            f"num_hidden_layers is {cfg['num_hidden_layers']}: no whole number "
            f"of periods of {period} layers"
        )
    return faults
