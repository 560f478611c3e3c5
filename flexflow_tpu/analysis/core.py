"""Analyzer core: program artifacts, violations, the check registry.

The analyzer is a *static* pass over what the compiler actually produced
— the ClosedJaxpr (tracing, free) and the compiled StableHLO text (AOT,
already paid for by the caller) — so every invariant it checks is a
property of the program, not of one lucky run.  Contrast the dynamic
ledgers (``executor.host_syncs``, the serve window counters): those
observe a behavior; a check here proves its absence class-wide
(docs/ANALYSIS.md).

Three consumers share this module (the "wire it in three places" of
ISSUE 10): ``tools/ffcheck.py`` (CLI), the ``--verify-compiled`` hook in
``runtime/executor.py`` / ``serve/engine.py``, and the search's golden
reconciliation tests.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

# 1 MiB: below this a missed donation is noise (scalar counters, token
# ids), above it a real double-HBM hazard the memory planner
# (search/memory.py) did not budget for.
DONATION_BYTES_FLOOR = 1 << 20
# closed-over host constants larger than this inside a jitted body are
# an un-prefetched H2D copy per dispatch
H2D_CONST_BYTES_FLOOR = 1 << 20
# fp32 operands smaller than this inside a bf16 region are deliberate
# precision islands (loss scalars, norm denominators), not leaks
DTYPE_LEAK_MIN_ELEMS = 4096


@dataclass
class Violation:
    """One invariant breach, with an op/file-level diagnostic."""

    check: str  # registry name: collective | transfer | donation | ...
    severity: str  # "error" | "warn"
    program: str  # artifact name (fit/eval/prefill/decode/...)
    message: str
    where: str = ""  # op + source location, or input path
    details: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        d = {
            "check": self.check,
            "severity": self.severity,
            "program": self.program,
            "message": self.message,
        }
        if self.where:
            d["where"] = self.where
        if self.details:
            d["details"] = self.details
        return d

    def __str__(self) -> str:
        loc = f" [{self.where}]" if self.where else ""
        return f"{self.severity}: {self.check} ({self.program}){loc}: {self.message}"


class AnalysisError(RuntimeError):
    """Raised under ``--verify-compiled strict`` when any check fails."""

    def __init__(self, report: "AnalysisReport") -> None:
        self.report = report
        super().__init__(
            "compiled-program verification failed "
            f"({len(report.violations)} violation(s)):\n"
            + report.format_human()
        )


@dataclass
class ProgramArtifact:
    """Everything the checks need about ONE compiled program.

    Built by the capture helpers (``flexflow_tpu.analysis.capture``) from
    a jitted callable's ``.trace()`` + AOT executable; fields a given
    deployment cannot supply stay ``None`` and the checks needing them
    skip (a serve engine has no ``Strategy``, so no collective
    reconciliation — the transfer/donation/dtype audits still run).
    """

    name: str  # display name, e.g. "fit", "serve.decode"
    role: str  # fit | eval | prefill | decode
    hlo: str = ""  # compiled StableHLO/HLO text (compiled.as_text())
    jaxpr: Any = None  # ClosedJaxpr, or None (HLO-only fallbacks apply)
    mesh: Any = None  # jax.sharding.Mesh, or None (single device)
    strategy: Any = None  # parallel.strategy.Strategy, or None
    layers: Any = None  # List[Layer] the strategy refers to, or None
    compute_dtype: str = "float32"
    # flat inputs: (label, shape, dtype-str, donated) per leaf, labels
    # like "params[dense1][kernel]"
    inputs: Sequence[Tuple[str, tuple, str, bool]] = ()
    # flat outputs: (shape, dtype-str) per leaf
    outputs: Sequence[Tuple[tuple, str]] = ()
    # params subtree of compiled.input_shardings: layer -> wname -> Sharding
    param_shardings: Any = None
    # ImpliedCollective list (search/cost.py); None disables the
    # collective reconciliation for this artifact
    implied: Any = None
    # donation is structurally impossible/meaningless for this program
    # (e.g. eval forward keeps params); the donation audit skips
    expects_donation: bool = True
    details: Dict[str, Any] = field(default_factory=dict)


class AnalysisReport:
    """Violations across one or more analyzed programs."""

    def __init__(self) -> None:
        self.violations: List[Violation] = []
        self.programs: List[str] = []

    def add_program(self, name: str) -> None:
        if name not in self.programs:
            self.programs.append(name)

    def extend(self, violations: Sequence[Violation]) -> None:
        self.violations.extend(violations)

    @property
    def ok(self) -> bool:
        return not self.violations

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for v in self.violations:
            out[v.check] = out.get(v.check, 0) + 1
        return out

    def to_dict(self) -> Dict[str, Any]:
        return {
            "schema": "ffcheck/1",
            "programs": list(self.programs),
            "ok": self.ok,
            "counts": self.counts(),
            "violations": [v.to_dict() for v in self.violations],
        }

    def to_json(self, indent: Optional[int] = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    def format_human(self) -> str:
        lines = []
        progs = ", ".join(self.programs) or "(none)"
        if self.ok:
            lines.append(f"ffcheck: OK — 0 violations across {progs}")
        else:
            lines.append(
                f"ffcheck: {len(self.violations)} violation(s) across {progs}"
            )
            for v in self.violations:
                lines.append("  " + str(v))
        return "\n".join(lines)


# --- check registry --------------------------------------------------------
# name -> fn(ProgramArtifact) -> List[Violation].  Checks must be total:
# an artifact missing their inputs yields [] (skip), never raises —
# docs/ANALYSIS.md "Adding a check".
CHECKS: Dict[str, Callable[[ProgramArtifact], List[Violation]]] = {}


def register_check(name: str):
    def deco(fn):
        CHECKS[name] = fn
        return fn

    return deco


def analyze_program(
    artifact: ProgramArtifact, checks: Optional[Sequence[str]] = None
) -> List[Violation]:
    """Run the registry (or the named subset) over one artifact."""
    # import for the registration side effect — checks live in their own
    # modules so the registry stays import-cycle free
    from flexflow_tpu.analysis import checks as _checks  # noqa: F401
    from flexflow_tpu.analysis import collectives as _coll  # noqa: F401

    names = list(checks) if checks is not None else sorted(CHECKS)
    out: List[Violation] = []
    for n in names:
        fn = CHECKS.get(n)
        if fn is None:
            raise KeyError(
                f"unknown check {n!r}; registered: {sorted(CHECKS)}"
            )
        out.extend(fn(artifact))
    return out


def analyze_artifacts(
    artifacts: Sequence[ProgramArtifact],
    checks: Optional[Sequence[str]] = None,
) -> AnalysisReport:
    report = AnalysisReport()
    for a in artifacts:
        report.add_program(a.name)
        report.extend(analyze_program(a, checks))
    return report


def flatten_info(tree: Any, label: str) -> List[Tuple[str, tuple, str, Any]]:
    """Flatten one pytree of ArgInfo/OutInfo-like leaves into
    ``(label+path, shape, dtype, donated-or-None)`` rows."""
    import jax

    rows = []
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        rows.append((
            label + jax.tree_util.keystr(path),
            tuple(getattr(leaf, "shape", ())),
            str(getattr(leaf, "dtype", "")),
            getattr(leaf, "donated", None),
        ))
    return rows


def eqn_where(eqn) -> str:
    """``file:line`` of the user frame that traced this jaxpr equation —
    the op-level diagnostic every violation carries when a jaxpr is
    available."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            return f"{frame.file_name}:{frame.start_line}"
    except Exception:
        pass
    return ""


def walk_jaxpr_eqns(jaxpr):
    """Yield every eqn in ``jaxpr`` and all nested sub-jaxprs (pjit
    bodies, scan/while/cond branches, custom_vjp closures)."""
    from jax.extend import core

    closed = getattr(jaxpr, "jaxpr", None)
    inner = closed if closed is not None and hasattr(closed, "eqns") else jaxpr
    for eqn in inner.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v, core):
                yield from walk_jaxpr_eqns(sub)


def _sub_jaxprs(v, core):
    if isinstance(v, core.ClosedJaxpr) or isinstance(v, core.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _sub_jaxprs(x, core)
