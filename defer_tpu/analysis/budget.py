"""Pass 3 — static perf-contract gate (`perf-contract`).

ROADMAP's hardware-tier item asks for tokens-per-dispatch and
kv-rows-read budget checks "so a future PR can't silently regress the
hot path". This pass makes the accounting behind those budgets
DECLARED state instead of prose: ``budgets.toml`` names each contract,
the obs counter that accounts for it and the hot functions that must
feed that counter. ``defer-analyze --budget budgets.toml`` then checks,
for every contract, that

    - the contract's counter is registered somewhere in the corpus
      (``reg.counter("defer_..."...)`` with a literal name);
    - every function the contract names exists AND reaches — through
      the same open-world callgraph the host-sync rule uses — at least
      one touch of the counter's pre-bound handle attribute
      (``self.obs.host_dispatches.inc()``). A hot loop that stops
      feeding its accounting counter is exactly the silent-regression
      failure mode: the number the tests and the benchmark read would
      go stale while still looking green.

The gate is static: it claims that the counter is fed, not what it
reads. The values are asserted by the CPU tests each contract's
description names and measured on the chip by ``perfbench/``. A file
that still carries a bound is rejected, so that none reads as enforced.

Findings report through the normal Finding stream (rule
``perf-contract``), so ``--strict --json`` consumers see budget state
next to lint state.

Python 3.10 has no ``tomllib``; a strict subset parser (tables,
strings, numbers, booleans, flat arrays) backs it so the gate needs
nothing the container doesn't have.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import Any

from defer_tpu.analysis.rules import Context, Finding

_OBS_KINDS = {"counter", "gauge", "histogram"}


class BudgetError(ValueError):
    """Malformed budgets file: bad TOML, or a contract missing/
    mistyping a required key."""


# -- TOML subset ------------------------------------------------------

_SECTION_RE = re.compile(r"^\[(?P<name>[A-Za-z0-9_.\-]+)\]$")
_KEY_RE = re.compile(r"^(?P<key>[A-Za-z0-9_\-]+)\s*=\s*(?P<val>.+)$")


def _strip_comment(line: str) -> str:
    """Drop a trailing # comment (quote-aware enough for this file's
    grammar: # inside a double-quoted string is kept)."""
    out = []
    in_str = False
    for ch in line:
        if ch == '"':
            in_str = not in_str
        elif ch == "#" and not in_str:
            break
        out.append(ch)
    return "".join(out).strip()


def _parse_value(raw: str, where: str) -> Any:
    raw = raw.strip()
    if raw.startswith('"') and raw.endswith('"') and len(raw) >= 2:
        return raw[1:-1]
    if raw in ("true", "false"):
        return raw == "true"
    if raw.startswith("[") and raw.endswith("]"):
        inner = raw[1:-1].strip()
        if not inner:
            return []
        return [
            _parse_value(part.strip(), where)
            for part in inner.split(",")
            if part.strip()
        ]
    try:
        return int(raw)
    except ValueError:
        pass
    try:
        return float(raw)
    except ValueError:
        raise BudgetError(
            f"{where}: unparseable value {raw!r} (the built-in TOML "
            "subset takes strings, numbers, booleans and flat arrays)"
        ) from None


def _parse_toml(text: str, path: str) -> dict[str, Any]:
    """budgets.toml -> nested dict, with a ``__line__`` entry per
    table so findings can point at the contract's declaration."""
    try:
        import tomllib  # Python >= 3.11

        data = tomllib.loads(text)
        # tomllib gives no line info; findings fall back to line 1.
        return data
    except ModuleNotFoundError:
        pass
    except Exception as e:  # malformed under the real parser
        raise BudgetError(f"{path}: {e}") from None
    root: dict[str, Any] = {}
    table = root
    for lineno, raw in enumerate(text.splitlines(), start=1):
        line = _strip_comment(raw)
        if not line:
            continue
        m = _SECTION_RE.match(line)
        if m:
            table = root
            for part in m.group("name").split("."):
                table = table.setdefault(part, {})
                if not isinstance(table, dict):
                    raise BudgetError(
                        f"{path}:{lineno}: table {m.group('name')!r} "
                        "collides with a value"
                    )
            table["__line__"] = lineno
            continue
        m = _KEY_RE.match(line)
        if m:
            table[m.group("key")] = _parse_value(
                m.group("val"), f"{path}:{lineno}"
            )
            continue
        raise BudgetError(f"{path}:{lineno}: unparseable line {raw!r}")
    return root


# -- contracts --------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Contract:
    name: str
    counter: str  # obs metric accounting for this contract
    functions: tuple[str, ...]  # hot functions that must feed it
    line: int  # declaration line in budgets.toml (1 if unknown)
    description: str = ""


# Keys that bounded numbers out of an artifact nothing produces now.
_MEASURED_KEYS = ("bench_section", "bench_metric", "max", "min")


def load_budgets(path: str) -> list[Contract]:
    with open(path, encoding="utf-8") as fh:
        data = _parse_toml(fh.read(), path)
    tables = data.get("contract")
    if not isinstance(tables, dict) or not any(
        isinstance(v, dict) for v in tables.values()
    ):
        raise BudgetError(
            f"{path}: no [contract.<name>] tables — nothing to enforce"
        )
    out: list[Contract] = []
    for name, tab in tables.items():
        if not isinstance(tab, dict):
            continue
        where = f"{path}: [contract.{name}]"
        counter = tab.get("counter")
        if not isinstance(counter, str) or not counter:
            raise BudgetError(f"{where}: missing `counter` (a string)")
        funcs = tab.get("functions")
        if not isinstance(funcs, list) or not all(
            isinstance(f, str) for f in funcs
        ):
            raise BudgetError(
                f"{where}: missing `functions` (array of strings)"
            )
        stale = [k for k in _MEASURED_KEYS if k in tab]
        if stale:
            raise BudgetError(
                f"{where}: {', '.join(f'`{k}`' for k in stale)}: the "
                "gate's measured half is gone — it is static and bounds "
                "no number. Assert the value in the test that produces "
                "it and name that test in `description`"
            )
        out.append(
            Contract(
                name=name,
                counter=counter,
                functions=tuple(funcs),
                line=int(tab.get("__line__", 1)),
                description=str(tab.get("description", "")),
            )
        )
    return out


# -- the check --------------------------------------------------------


def _metric_handles(ctx: Context) -> dict[str, set[str]]:
    """metric name -> attribute names its pre-bound handles are stored
    under (``self.host_dispatches = reg.counter("defer_host_..."``
    maps the metric to {"host_dispatches"})."""
    out: dict[str, set[str]] = {}
    for mod in ctx.modules:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Assign):
                continue
            calls = [node.value]
            # handles built in comprehensions/dicts still carry the
            # literal name; find any obs-kind call in the value expr
            calls = [
                c
                for c in ast.walk(node.value)
                if isinstance(c, ast.Call)
                and isinstance(c.func, ast.Attribute)
                and c.func.attr in _OBS_KINDS
            ]
            for call in calls:
                if not (
                    call.args
                    and isinstance(call.args[0], ast.Constant)
                    and isinstance(call.args[0].value, str)
                ):
                    continue
                metric = call.args[0].value
                for tgt in node.targets:
                    if isinstance(tgt, ast.Attribute):
                        out.setdefault(metric, set()).add(tgt.attr)
                    elif isinstance(tgt, ast.Name):
                        out.setdefault(metric, set()).add(tgt.id)
    return out


def _touches(fn_node: ast.AST, attrs: set[str]) -> bool:
    for node in ast.walk(fn_node):
        if isinstance(node, ast.Attribute) and node.attr in attrs:
            return True
    return False


def check_static(
    ctx: Context, contracts: list[Contract], budget_path: str
) -> tuple[list[Finding], list[dict[str, str]]]:
    """Registration + reachable-touch checks. Returns the findings,
    which point at the contract's declaration in budgets.toml, and a
    JSON-ready verdict per contract: ``fail`` where it raised any."""
    handles = _metric_handles(ctx)
    out: list[Finding] = []
    verdicts: list[dict[str, str]] = []
    for c in contracts:
        found = _check_contract(ctx, c, handles.get(c.counter), budget_path)
        out.extend(found)
        verdicts.append(
            {
                "contract": c.name,
                "counter": c.counter,
                "status": "fail" if found else "pass",
            }
        )
    return out, verdicts


def _check_contract(
    ctx: Context, c: Contract, attrs: set[str] | None, budget_path: str
) -> list[Finding]:
    out: list[Finding] = []
    if not attrs:
        out.append(
            Finding(
                "perf-contract",
                budget_path,
                c.line,
                0,
                f"[contract.{c.name}] accounts through "
                f"{c.counter!r} but no analyzed module registers "
                "that metric — the contract can never be measured",
            )
        )
        return out
    for fname in c.functions:
        cands = ctx.graph.by_name.get(fname, [])
        if not cands:
            out.append(
                Finding(
                    "perf-contract",
                    budget_path,
                    c.line,
                    0,
                    f"[contract.{c.name}] names hot function "
                    f"{fname!r}, which does not exist in the "
                    "analyzed corpus",
                )
            )
            continue
        # BFS from the named functions; ANY candidate chain
        # touching the handle satisfies the contract (both decode
        # servers define `_tick`; each feeds the shared metric).
        seen: set[int] = set()
        frontier = list(cands)
        found = False
        while frontier and not found:
            fi = frontier.pop()
            if id(fi.node) in seen:
                continue
            seen.add(id(fi.node))
            if _touches(fi.node, attrs):
                found = True
                break
            for bare, calls in (
                (True, fi.calls_bare),
                (False, fi.calls_attr),
            ):
                for callee in calls:
                    frontier.extend(
                        r
                        for r in ctx.graph.resolve_call(
                            fi, callee, bare
                        )
                        if id(r.node) not in seen
                    )
        if not found:
            out.append(
                Finding(
                    "perf-contract",
                    budget_path,
                    c.line,
                    0,
                    f"[contract.{c.name}]: nothing reachable from "
                    f"`{fname}` touches the {c.counter!r} handle "
                    f"({'/'.join(sorted(attrs))}) — the hot loop "
                    "stopped feeding its accounting counter, so "
                    "the budget would go stale while looking green",
                )
            )
    return out
