"""``cache-branding``: what the read kept must reach the cache key.

Device-cache entries are keyed by ``scan_key``: the immutable file-set
identity, extended by ``_pruned_scan_key`` with the kept signature of the
read that produced the batch (which row groups of which files survived;
None for a whole read) — never with the predicate. A call site that drops
the kwarg doesn't fail — it silently caches a pruned batch under the whole
file set's key, so a later read that kept *other* rows reuses stale device
buffers. This rule enforces the three call-site contracts:

1. ``…._filter_mask(...)`` must pass ``kept=`` explicitly,
2. ``device_filter_mask(...)`` must pass ``scan_key=`` (kwarg or the
   4th positional),
3. ``stage_filter_columns(...)`` must pass ``scan_key`` likewise.

``scan_key=None`` is fine — that is an explicit "transient batch, don't
cache" decision — and so is ``kept=None``, an explicit "this batch is the
whole file set", visible at the call site.
"""

from __future__ import annotations

import ast
from typing import List

from hyperspace_tpu.check.findings import Finding
from hyperspace_tpu.check.rules import Rule

NAME = "cache-branding"

# callee name -> (required kwarg, positional index that also satisfies it)
_CONTRACTS = {
    "_filter_mask": ("kept", None),
    "device_filter_mask": ("scan_key", 3),
    "stage_filter_columns": ("scan_key", 3),
}


def _callee_name(fn: ast.AST):
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        return fn.id
    return None


def scan_tree(tree: ast.Module) -> List[ast.Call]:
    """Calls in the tree that violate a branding contract."""
    bad: List[ast.Call] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _callee_name(node.func)
        contract = _CONTRACTS.get(name)
        if contract is None:
            continue
        kwarg, pos = contract
        if any(kw.arg == kwarg for kw in node.keywords):
            continue
        if any(kw.arg is None for kw in node.keywords):
            continue  # **kwargs forwarding — assume the caller threads it
        if pos is not None and len(node.args) > pos:
            continue
        bad.append(node)
    return bad


def check(ctx) -> List[Finding]:
    findings: List[Finding] = []
    for path in ctx.files:
        rel = ctx.relpath(path)
        for call in scan_tree(ctx.ast_of(path)):
            name = _callee_name(call.func)
            kwarg, _ = _CONTRACTS[name]
            findings.append(
                Finding(
                    rule=NAME,
                    path=rel,
                    line=call.lineno,
                    message=(
                        f"call to {name}() drops the cache-branding kwarg {kwarg!r}; "
                        f"pass {kwarg}=... explicitly (None is fine, silence is not)"
                    ),
                )
            )
    return findings


RULE = Rule(name=NAME, doc=__doc__.strip(), check=check)
