"""Compiled-HLO program-contract lint.

The framework's headline claims are *compiled-program properties* (SURVEY.md
§2.9, mirroring the reference's shuffle-freedom guarantee, ref:
HS/index/covering/JoinIndexRule.scala:604-618): the bucketed SMJ span program
is collective-free, the sharded grouped aggregate all-gathers fixed-size
partial tables and never rows, the distributed index build exchanges rows
with exactly ONE all-to-all. This module is the rule engine that holds
every program family to such a claim:

- each device-program family **declares** its collective budget and
  forbidden-op patterns at registration (:func:`register_contract`, called
  from ``exec/device.py`` / ``ops/bucketize.py`` next to the program
  builders),
- :func:`verify_hlo` checks any compiled HLO text against a declared
  contract and returns :class:`~hyperspace_tpu.check.findings.Finding`s,
- :func:`maybe_verify` is the runtime hook: default-off behind
  ``hyperspace.check.hlo.enabled``, it verifies every *newly compiled*
  executable (once per (program-cache key, shape signature)) at
  program-cache-fill time, bumping ``hs_check_violations_total{rule,program}``
  and ``hs_check_programs_verified_total{program}``.

The disabled path is one conf-dict lookup.
"""

from __future__ import annotations

import re
import threading
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from hyperspace_tpu.check.findings import Finding

# --------------------------------------------------------------------------
# HLO text scanning (``hyperspace_tpu.parallel`` re-exports these names)
# --------------------------------------------------------------------------

COLLECTIVE_OPS = (
    "all-to-all",
    "all-gather",
    "collective-permute",
    "all-reduce",
    "reduce-scatter",
)

# an HLO op application site: ` op-name(` or ` op-name-start(` — the result
# type before it may be a tuple containing spaces, so key on the call itself;
# operand mentions like `get-tuple-element(%all-to-all)` don't match (no
# following paren), and metadata op_name strings use underscores, not dashes.
# Async pairs (op-start/op-done) count once at -start.
_INSTR = re.compile(
    r"[\s)](" + "|".join(COLLECTIVE_OPS) + r")(-start|-done)?(?:\.\d+)?\("
)


def collective_counts(hlo_text: str) -> Dict[str, int]:
    """Occurrences of each collective op in compiled HLO text (async
    start/done pairs counted once)."""
    counts = {k: 0 for k in COLLECTIVE_OPS}
    for m in _INSTR.finditer(hlo_text):
        if m.group(2) == "-done":
            continue
        counts[m.group(1)] += 1
    return counts


def assert_collectives(hlo_text: str, expect: Dict[str, int], context: str = "") -> None:
    """Assert exact counts for the ops named in ``expect`` and ZERO for every
    other collective op."""
    got = collective_counts(hlo_text)
    for op in COLLECTIVE_OPS:
        want = expect.get(op, 0)
        assert got[op] == want, (
            f"{context or 'program'}: expected {want} x {op} in compiled HLO, "
            f"found {got[op]} (all counts: {got})"
        )


# ops that move row data between devices: their absence is the reference's
# shuffle-freedom claim (ref: JoinIndexRule.scala:604-618). all-reduce stays
# out of this set — a scalar reduction is not a data shuffle.
SHUFFLE_OPS = ("all-to-all", "all-gather", "collective-permute", "reduce-scatter")


def assert_shuffle_free(hlo_text: str, context: str = "") -> None:
    """Assert the compiled program exchanges NO row data between devices
    (no all-to-all / all-gather / collective-permute / reduce-scatter)."""
    got = collective_counts(hlo_text)
    bad = {op: got[op] for op in SHUFFLE_OPS if got[op]}
    assert not bad, (
        f"{context or 'program'}: expected a shuffle-free program but the "
        f"compiled HLO contains data-movement collectives {bad} "
        f"(all counts: {got})"
    )


def hlo_text_of(jitted, *args, **kwargs) -> str:
    """Compiled HLO text of a jitted callable for the given example
    arguments — the artifact the rules inspect."""
    return jitted.lower(*args, **kwargs).compile().as_text()


# --------------------------------------------------------------------------
# Forbidden-op text rules (apply to every family unless opted out)
# --------------------------------------------------------------------------

_INSTRUCTION = re.compile(r"^\s*(ROOT )?(%[\w.\-]+) = (\([^=]*?\)|\S+) ([\w\-]+)\((.*)$")
_ARRAY_F64 = re.compile(r"f64\[\d")
_NAME = re.compile(r"%[\w.\-]+")


class _F64Upcast:
    """The ``f64-upcast`` rule: a whole-array ``f64[n] convert(f32[n])``,
    with ``search`` as a compiled pattern has it.

    One shape of it is not an upcast of data but the resident form of an
    8-byte column read back (``exec/device.join_planes``): the column's two
    f32 planes converted and added, ``add(convert(head), convert(tail))``.
    That join is told apart by where it sits and by who reads it: inside a
    fused computation (not the entry computation, not a computation's
    ``ROOT``: nothing of it is written to memory), each convert read by one
    instruction only, an ``add`` whose other operand is such a convert too
    and which is not the computation's ``ROOT`` either (the joined column
    written out is the 8-byte array back). Any other convert of an f32 array
    to f64 is reported: one that stands in the entry computation or is a
    fusion's result (materialised, twice the bytes), and one that feeds
    anything but that add (arithmetic carried out in f64 over f32 data). An
    operand's type is read from the operand where the text prints it and
    from the operand's own definition where not."""

    def search(self, hlo_text: str):
        # a computation is the lines between a header that ends in "{" and
        # the "}" that closes it; loose lines count as unfused
        block, unfused = [], True
        for line in hlo_text.splitlines() + ["}"]:
            header = line.rstrip().endswith("{") and " = " not in line
            if not header and not line.startswith("}"):
                block.append(line)
                continue
            found = self._in_block(block, unfused)
            if found:
                return found
            block, unfused = [], not header or line.startswith("ENTRY")
        return None

    @staticmethod
    def _in_block(lines, unfused: bool):
        """The first upcast among ``lines`` (one computation's instructions)
        that is not the fused join, as a match of its text; None if none."""
        defs = {}  # name -> (is ROOT, result type, opcode, operand text, operand names, match)
        for line in lines:
            m = _INSTRUCTION.match(line)
            if m:
                root, name, result, opcode, rest = m.groups()
                operands = rest.split(")", 1)[0]
                defs[name] = (bool(root), result, opcode, operands, _NAME.findall(operands), m)
        upcasts = {}
        for name, (root, result, opcode, operands, sources, m) in defs.items():
            if opcode != "convert" or not _ARRAY_F64.match(result):
                continue
            source = defs.get(sources[0]) if sources else None
            if operands.lstrip().startswith("f32[") or (source and source[1].startswith("f32[")):
                upcasts[name] = (root, m)
        for name, (root, m) in upcasts.items():
            readers = [d for d in defs.values() if name in d[4]]
            joined = (
                not unfused and not root and len(readers) == 1 and readers[0][0] is False and readers[0][2] == "add"
                and len(readers[0][4]) == 2 and all(o in upcasts for o in readers[0][4])
            )
            if not joined:
                return re.search(r"f64\[.*", m.group(0).strip())
        return None


#: (rule name, pattern, human description); a pattern is whatever has a
#: ``search(text)`` that returns a match or None. These encode device-program
#: hygiene independent of the collective story: a device program must never
#: round-trip through the host mid-flight (python callbacks, infeed/outfeed),
#: must not silently double an array's HBM footprint by upcasting f32 data to
#: f64, and must not carry bounded-dynamic dimensions (``s32[<=N]``), whose
#: shape-dependent control flow defeats the one-executable-per-bucket design.
FORBIDDEN_PATTERNS: Tuple[Tuple[str, "re.Pattern", str], ...] = (
    (
        "host-callback",
        re.compile(
            r"\binfeed\(|\boutfeed\(|custom_call_target=\"[^\"]*(?:python|host_callback|callback)[^\"]*\""
        ),
        "host round-trip (infeed/outfeed/python callback custom-call) inside a device program",
    ),
    (
        "f64-upcast",
        _F64Upcast(),
        "whole-array f32->f64 convert (doubles HBM footprint; stage f64 or compute in f32); the fused join of a "
        "resident column's two f32 planes, add(convert(head), convert(tail)) inside a fusion, is not one",
    ),
    (
        "dynamic-shape",
        re.compile(r"\[<=\d"),
        "bounded-dynamic dimension (recompile/slow-path hazard; pad to a shape bucket instead)",
    ),
)


# --------------------------------------------------------------------------
# Contracts
# --------------------------------------------------------------------------

_ANY = (0, None)


@dataclass(frozen=True)
class ProgramContract:
    """Declared collective budget for one device-program family.

    ``collectives`` maps op name -> (min, max) occurrences in the compiled
    HLO (``max=None`` = unbounded). Ops not listed must not appear at all —
    a contract says everything it permits. ``forbid`` names which
    :data:`FORBIDDEN_PATTERNS` rules apply (default: all).

    Every family's executable is named after it: compiled text whose
    ``HloModule`` is not ``jit_hs_<family>`` (:func:`program_name`) violates
    rule ``program-name``.

    ``single_fusion`` asserts the whole-plan-fusion guarantee: the family
    compiles to ONE executable — exactly one ``HloModule`` with exactly one
    ``ENTRY`` computation in the compiled text. (Backends still split an
    entry into internal ``fusion`` computations; the per-stage promise is
    one module and one entry, i.e. one dispatch, not one backend kernel.)
    """

    family: str
    collectives: Dict[str, Tuple[int, Optional[int]]] = field(default_factory=dict)
    forbid: Tuple[str, ...] = tuple(name for name, _, _ in FORBIDDEN_PATTERNS)
    description: str = ""
    single_fusion: bool = False


_MODULE_NAME = re.compile(r"^HloModule ([^\s,]+)", re.MULTILINE)

_CONTRACTS: Dict[str, ProgramContract] = {}
_CONTRACTS_LOCK = threading.Lock()


def register_contract(
    family: str,
    collectives: Optional[Dict[str, Tuple[int, Optional[int]]]] = None,
    forbid: Optional[Tuple[str, ...]] = None,
    description: str = "",
    single_fusion: bool = False,
) -> ProgramContract:
    """Declare (or re-declare, idempotently) a program family's contract.
    Called next to the program builders so the budget lives with the code it
    constrains."""
    c = ProgramContract(
        family=family,
        collectives=dict(collectives or {}),
        forbid=tuple(forbid) if forbid is not None else tuple(n for n, _, _ in FORBIDDEN_PATTERNS),
        description=description,
        single_fusion=bool(single_fusion),
    )
    with _CONTRACTS_LOCK:
        _CONTRACTS[family] = c
    return c


def program_name(family: str) -> str:
    """The stable function name of a family's device program: its executable
    is ``jit_<this>`` in the profiler's module line, in HLO dumps and in the
    compile-cache log."""
    return "hs_" + family.replace("-", "_")


def named(family: str, fn):
    """``fn`` under its family's :func:`program_name`, for ``jax.jit`` to name
    the module after. A family that no contract declares is a KeyError: the
    names and the contracts are one list."""
    if family not in _CONTRACTS:
        raise KeyError(f"no contract registered for program family {family!r}")
    fn.__name__ = fn.__qualname__ = program_name(family)
    return fn


def contract_for(family: str) -> Optional[ProgramContract]:
    with _CONTRACTS_LOCK:
        return _CONTRACTS.get(family)


def registered_contracts() -> Dict[str, ProgramContract]:
    with _CONTRACTS_LOCK:
        return dict(_CONTRACTS)


def verify_hlo(family: str, hlo_text: str, program: str = "") -> List[Finding]:
    """Check compiled HLO text against ``family``'s declared contract.
    Returns one Finding per violated rule (empty = conformant). Raises
    KeyError for an undeclared family — an unknown family is a lint bug,
    not a clean program."""
    contract = contract_for(family)
    if contract is None:
        raise KeyError(
            f"no contract registered for program family {family!r} "
            f"(registered: {sorted(_CONTRACTS)})"
        )
    label = program or family
    findings: List[Finding] = []
    module = _MODULE_NAME.search(hlo_text)
    if module is not None and module.group(1) != "jit_" + program_name(family):
        # the profiler, HLO dumps and the compile-cache log know a program by
        # its module: one that does not carry its family's name is invisible
        # to every reader that looks for jit_hs_<family>
        findings.append(
            Finding(
                rule="program-name",
                path=f"hlo:{label}",
                line=0,
                message=(
                    f"{family}: compiled module is {module.group(1)!r}, expected "
                    f"'jit_{program_name(family)}' (wrap the function in hlo_lint.named())"
                ),
                detail={"family": family, "module": module.group(1)},
            )
        )
    got = collective_counts(hlo_text)
    for op in COLLECTIVE_OPS:
        lo, hi = contract.collectives.get(op, (0, 0))
        n = got[op]
        if n < lo or (hi is not None and n > hi):
            budget = f"exactly {lo}" if lo == hi else (
                f">= {lo}" if hi is None else f"{lo}..{hi}"
            )
            findings.append(
                Finding(
                    rule=f"collective-budget:{op}",
                    path=f"hlo:{label}",
                    line=0,
                    message=(
                        f"{family}: {n} x {op} in compiled HLO, contract allows "
                        f"{budget} (all counts: {got})"
                    ),
                    detail={"family": family, "op": op, "count": n},
                )
            )
    if contract.single_fusion:
        n_mod = len(re.findall(r"^HloModule\b", hlo_text, flags=re.MULTILINE))
        n_entry = len(re.findall(r"^ENTRY\b", hlo_text, flags=re.MULTILINE))
        if n_mod != 1 or n_entry != 1:
            findings.append(
                Finding(
                    rule="single-fusion",
                    path=f"hlo:{label}",
                    line=0,
                    message=(
                        f"{family}: whole-plan-fusion contract expects ONE "
                        f"executable (1 HloModule / 1 ENTRY), compiled text "
                        f"has {n_mod} module(s) / {n_entry} entry computation(s)"
                    ),
                    detail={"family": family, "modules": n_mod, "entries": n_entry},
                )
            )
    active = {name for name in contract.forbid}
    for name, pat, desc in FORBIDDEN_PATTERNS:
        if name not in active:
            continue
        m = pat.search(hlo_text)
        if m:
            findings.append(
                Finding(
                    rule=f"forbidden-op:{name}",
                    path=f"hlo:{label}",
                    line=0,
                    message=f"{family}: {desc} (matched {m.group(0)!r})",
                    detail={"family": family, "match": m.group(0)},
                )
            )
    return findings


def assert_contract(family: str, hlo_text: str, program: str = "") -> None:
    """Rule-engine flavor of the old ``assert_collectives``: raise
    AssertionError listing every violation."""
    findings = verify_hlo(family, hlo_text, program)
    assert not findings, "HLO contract violations:\n" + "\n".join(
        f.render() for f in findings
    )


# --------------------------------------------------------------------------
# Runtime hook: verify at program-cache-fill time
# --------------------------------------------------------------------------

#: module-level default for call sites with no session conf in reach (the
#: index-build exchange); the most recently constructed Session's conf wins,
#: same stance as exec/io.py's decode-thread pool width.
_default_enabled = False

_VERIFIED_SEEN: set = set()
_SEEN_LOCK = threading.Lock()
_VIOLATIONS: List[Finding] = []

_CONF_KEY = "hyperspace.check.hlo.enabled"


def set_default_enabled(on: bool) -> None:
    global _default_enabled
    _default_enabled = bool(on)


def reset_runtime_state() -> None:
    """Forget which executables were verified and the violation log (tests)."""
    with _SEEN_LOCK:
        _VERIFIED_SEEN.clear()
        del _VIOLATIONS[:]


def runtime_violations() -> List[Finding]:
    with _SEEN_LOCK:
        return list(_VIOLATIONS)


def _enabled(conf) -> bool:
    if conf is None:
        return _default_enabled
    return bool(conf.get(_CONF_KEY))


def maybe_verify(conf, family: str, key, jitted, args, kwargs=None) -> None:
    """Verify ``jitted``'s compiled HLO for ``args`` against ``family``'s
    contract — once per (program-cache key, shape signature), only when
    ``hyperspace.check.hlo.enabled`` (or the module default, for sites with
    no conf in reach) is on.

    Violations are counted in ``hs_check_violations_total{rule,program}``,
    kept readable via :func:`runtime_violations`, and surfaced as a warning —
    never an exception: a production query must not die because a compiler
    upgrade re-shaped its HLO; the metric is the alarm.
    """
    if not _enabled(conf):
        return
    import jax

    sig = tuple(
        tuple(a.shape) if hasattr(a, "shape") else repr(type(a))
        for a in jax.tree_util.tree_leaves((args, kwargs or {}))
    )
    seen_key = (key, sig)
    with _SEEN_LOCK:
        if seen_key in _VERIFIED_SEEN:
            return
        _VERIFIED_SEEN.add(seen_key)
    try:
        text = hlo_text_of(jitted, *args, **(kwargs or {}))
    except Exception as exc:  # lowering quirks must not take the query down
        warnings.warn(f"hscheck: could not lower {family} program for verification: {exc}")
        return
    findings = verify_hlo(family, text, program=str(key))
    from hyperspace_tpu.obs.metrics import REGISTRY

    REGISTRY.counter(
        "hs_check_programs_verified_total",
        "Compiled executables verified against their declared HLO contract",
        program=family,
    ).inc()
    for f in findings:
        REGISTRY.counter(
            "hs_check_violations_total",
            "Program-contract and invariant violations detected by hscheck",
            rule=f.rule,
            program=family,
        ).inc()
        warnings.warn(f"hscheck HLO contract violation: {f.render()}")
    if findings:
        with _SEEN_LOCK:
            _VIOLATIONS.extend(findings)
