"""vescale-lint — AST enforcement of the framework invariants PRs 1-5
established by convention.

Five rules, each a lesson this codebase already paid for once:

  VSC201  every ``VESCALE_*`` env READ goes through ``analysis.envreg``
          (``os.environ.get``/``os.getenv``/``[...]``/``in`` of a
          VESCALE name outside the registry module).  Writes —
          ``os.environ[...] = ``, ``setdefault``, ``pop``, ``del`` — are
          config propagation to children and stay legal.
  VSC202  every ``VESCALE_*`` string literal names a REGISTERED var (or a
          prefix of one, for docstring families like VESCALE_IO_BACKOFF_*)
          — unregistered knobs are undocumented knobs.
  VSC203  a rebindable module hook slot (any name declared ``global`` in
          some function, or containing "hook") must never be bound to a
          lambda: the gating contract asserts dormant hooks by IDENTITY
          against module-level named no-op functions.
  VSC204  a function installed via ``signal.signal`` must stay
          async-signal-safe: no lock construction/acquisition, no IO, no
          logging, no array allocation in the handler frame.
  VSC205  no bare ``except:`` (or ``except BaseException:``) without a
          re-raise inside a loop — retry loops that swallow
          ``KeyboardInterrupt`` cannot be Ctrl-C'd out of.
  VSC206  every ``pallas_call`` lives under ``vescale_tpu/kernels/`` —
          kernels reached any other way bypass the ``VESCALE_KERNELS``
          dispatch contract (off-mode byte-identity, interpret-mode
          parity coverage, dispatch/fallback telemetry; docs/kernels.md).
  VSC207  no ad-hoc warn-once latches: a function that both calls
          ``warnings.warn``/``<log>.warning`` AND touches a "warned"
          latch is a hand-rolled alert with no lifecycle — no resolve,
          no dedup window, no /alerts visibility.  Route it through
          ``telemetry.alerts.raise_alert`` (the engine dedups and
          resolves) or annotate the legacy fallback.  The alert engine
          itself (telemetry/alerts.py owns the ONE sanctioned fallback
          latch) is exempt.

  VSC208  a priced decision must enter the cost-audit ledger: PACKAGE
          code (files under vescale_tpu/ — tests and scripts
          call the cost model to inspect it, not to decide) that calls
          ``simulate_schedule``/``estimate_stage_costs`` inside a
          function with no ``record_prediction`` reference is choosing
          by a prediction nobody will ever audit against reality
          (telemetry/costaudit.py).  Record the prediction, or annotate
          the site.

Plus VSC104 (shared with shardcheck): collective calls under
rank-divergent ``if``/``while`` conditions — the classic SPMD deadlock.

Suppression: append ``# vescale-lint: disable=VSC201`` (comma-separated
codes, or ``disable=all``) to the offending line.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .findings import CODES, Finding, FindingReport

__all__ = [
    "lint_source",
    "lint_paths",
    "rank_divergence_findings",
    "iter_python_files",
]

_ENV_NAME = re.compile(r"VESCALE_[A-Z0-9_]+")
_DISABLE = re.compile(r"#\s*vescale-lint:\s*disable=([A-Za-z0-9,_ ]+|all)")

# names whose call inside a signal handler frame is unsafe (locks, IO,
# logging, allocation); attribute calls checked against the same set
_SIGNAL_UNSAFE = {
    "acquire", "wait", "join", "Lock", "RLock", "Condition", "Semaphore",
    "BoundedSemaphore", "open", "print", "log", "debug", "info", "warning",
    "error", "exception", "write", "flush", "array", "asarray", "zeros",
    "ones", "empty",
}

# rank-ish identifiers in a condition that make control flow rank-divergent
_RANK_TOKENS = {
    "rank", "process_id", "process_index", "coordinate_of_rank",
    "local_rank", "host_id", "is_coordinator",
}
# collective entry points whose divergent execution deadlocks the mesh
_COLLECTIVE_CALLS = {
    "barrier", "all_processes_ok", "allgather_ints", "mesh_all_reduce",
    "mesh_all_gather", "mesh_reduce_scatter", "mesh_all_to_all",
    "mesh_broadcast", "mesh_scatter", "mesh_ppermute", "psum", "pmean",
    "pmax", "pmin", "psum_scatter", "all_gather", "all_to_all", "ppermute",
    "all_gather_object", "all_reduce", "reduce_scatter", "broadcast",
}
# rank-guarded SINGLE-WRITER idioms that are fine (no collective inside)
_CALLS_EXEMPT_FROM_RANK_GUARD: Set[str] = set()

# cost-model entry points whose callers are PRICING a decision (VSC208):
# a package function that ranks/chooses by these without recording the
# prediction produces a cost nobody ever audits
_PRICED_CALLS = {"simulate_schedule", "estimate_stage_costs"}


def _disabled_codes(lines: Sequence[str], lineno: int) -> Set[str]:
    if 1 <= lineno <= len(lines):
        m = _DISABLE.search(lines[lineno - 1])
        if m:
            raw = m.group(1)
            if raw.strip() == "all":
                return {"all"}
            return {c.strip().upper() for c in raw.split(",") if c.strip()}
    return set()


class _Lint(ast.NodeVisitor):
    def __init__(self, filename: str, source: str, registered) -> None:
        self.filename = filename
        self.lines = source.splitlines()
        self.registered = registered
        self.findings: List[Finding] = []
        self._global_slots: Set[str] = set()
        self._handler_names: Set[str] = set()
        self._loop_depth = 0
        self._is_envreg = os.path.basename(filename) == "envreg.py"
        parts = os.path.normpath(filename).split(os.sep)
        # VSC207 exemption: the alert engine owns the one sanctioned
        # warn-once latch (its dormant-mode raise_alert fallback)
        self._is_alerts = any(
            a == "telemetry" and b == "alerts.py"
            for a, b in zip(parts, parts[1:])
        )
        self._vsc207_seen: Set[int] = set()
        self._vsc208_seen: Set[int] = set()
        # VSC208 applies only to package code: tests and scripts call
        # the cost model to inspect it, not to decide by it
        self._in_package = "vescale_tpu" in parts
        # exempt ONLY the vescale_tpu/kernels package itself — a nested
        # .../kernels/ directory elsewhere is still subject to VSC206
        self._in_kernels = any(
            a == "vescale_tpu" and b == "kernels"
            for a, b in zip(parts, parts[1:])
        )

    # ------------------------------------------------------------ plumbing
    def emit(self, code: str, message: str, node: ast.AST) -> None:
        lineno = getattr(node, "lineno", 0)
        disabled = _disabled_codes(self.lines, lineno)
        if "all" in disabled or code in disabled:
            return
        self.findings.append(Finding(
            CODES[code], message, where=f"{self.filename}:{lineno}"
        ))

    # two-pass: collect global-slot names and signal handlers first
    def prepass(self, tree: ast.Module) -> None:
        for node in ast.walk(tree):
            if isinstance(node, ast.Global):
                self._global_slots.update(node.names)
            if isinstance(node, ast.Call) and _dotted(node.func) in (
                "signal.signal", "signal"
            ):
                if len(node.args) >= 2:
                    h = node.args[1]
                    name = h.attr if isinstance(h, ast.Attribute) else (
                        h.id if isinstance(h, ast.Name) else None
                    )
                    if name:
                        self._handler_names.add(name)

    # -------------------------------------------------------- VSC201 / 202
    def _check_env_name(self, name: str, node: ast.AST) -> None:
        ok = self.registered(name)
        if not ok:
            self.emit(
                "VSC202",
                f"{name} is not registered in analysis.envreg — declare it "
                "(name/type/default/doc) or fix the name",
                node,
            )

    def _flag_env_read(self, name: str, node: ast.AST) -> None:
        if self._is_envreg:
            return
        self.emit(
            "VSC201",
            f"direct environment read of {name}; use "
            "vescale_tpu.analysis.envreg accessors (get_bool/get_int/"
            "get_float/get_str/get_raw)",
            node,
        )

    def visit_Call(self, node: ast.Call) -> None:
        dotted = _dotted(node.func)
        # ------------------------------------------------------- VSC206
        # any `pallas_call` spelling (pl.pallas_call, pallas.pallas_call,
        # bare pallas_call) outside the kernels package
        if not self._in_kernels and (
            dotted == "pallas_call" or dotted.endswith(".pallas_call")
        ):
            self.emit(
                "VSC206",
                "direct pallas_call outside vescale_tpu/kernels/ bypasses "
                "the VESCALE_KERNELS dispatch contract; move the kernel "
                "into the kernels package and dispatch through it",
                node,
            )
        # os.getenv("X") / os.environ.get("X") / os.environ.pop (write-ish: pop allowed)
        if dotted in ("os.getenv", "getenv", "os.environ.get", "environ.get"):
            if node.args and isinstance(node.args[0], ast.Constant) and isinstance(node.args[0].value, str):
                name = node.args[0].value
                if name.startswith("VESCALE_"):
                    self._flag_env_read(name, node)
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript) -> None:
        # os.environ["X"] in Load context is a read; Store/Del are writes
        if isinstance(node.ctx, ast.Load) and _dotted(node.value) in ("os.environ", "environ"):
            sl = node.slice
            if isinstance(sl, ast.Constant) and isinstance(sl.value, str) and sl.value.startswith("VESCALE_"):
                self._flag_env_read(sl.value, node)
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare) -> None:
        # "X" in os.environ is a read probe
        if (
            isinstance(node.left, ast.Constant)
            and isinstance(node.left.value, str)
            and node.left.value.startswith("VESCALE_")
            and any(isinstance(op, (ast.In, ast.NotIn)) for op in node.ops)
            and any(_dotted(c) in ("os.environ", "environ") for c in node.comparators)
        ):
            self._flag_env_read(node.left.value, node)
        self.generic_visit(node)

    def visit_Constant(self, node: ast.Constant) -> None:
        if isinstance(node.value, str):
            for name in _ENV_NAME.findall(node.value):
                self._check_env_name(name, node)
        self.generic_visit(node)

    # ------------------------------------------------------------- VSC203
    def visit_Assign(self, node: ast.Assign) -> None:
        if isinstance(node.value, ast.Lambda):
            for t in node.targets:
                name = t.id if isinstance(t, ast.Name) else (
                    t.attr if isinstance(t, ast.Attribute) else None
                )
                if name and (name in self._global_slots or "hook" in name.lower()):
                    self.emit(
                        "VSC203",
                        f"hook slot {name!r} bound to a lambda; bind a "
                        "module-level named no-op function so dormant hooks "
                        "can be identity-asserted",
                        node,
                    )
        self.generic_visit(node)

    # ------------------------------------------------------------- VSC207
    def _check_warn_latch(self, node: ast.FunctionDef) -> None:
        """A function that both warns and reads/writes a "warned" latch is
        rolling its own alert lifecycle.  The finding anchors to the warn
        call (that's the line to migrate or annotate)."""
        if self._is_alerts:
            return
        warn_calls = []
        has_latch = False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                name = _dotted(sub.func).rsplit(".", 1)[-1]
                if name in ("warn", "warning"):
                    warn_calls.append(sub)
            ident = None
            if isinstance(sub, ast.Name):
                ident = sub.id
            elif isinstance(sub, ast.Attribute):
                ident = sub.attr
            elif isinstance(sub, ast.Constant) and isinstance(sub.value, str):
                ident = sub.value
            if ident is not None and "warned" in ident.lower():
                has_latch = True
        if has_latch:
            for call in warn_calls:
                # a def nested in a flagged def would re-flag the same
                # call — one finding per warn site
                if id(call) in self._vsc207_seen:
                    continue
                self._vsc207_seen.add(id(call))
                self.emit(
                    "VSC207",
                    f"warn-once latch in {node.name!r}: a hand-rolled alert "
                    "with no lifecycle (no resolve, no dedup window, no "
                    "/alerts visibility) — raise it through telemetry.alerts."
                    "raise_alert, or annotate the legacy fallback",
                    call,
                )

    # ------------------------------------------------------------- VSC208
    def _check_priced_decision(self, node: ast.FunctionDef) -> None:
        """A package function that calls a cost-model entry point but never
        references ``record_prediction`` is pricing a decision outside the
        audit ledger.  The finding anchors to the priced call; a function
        that records (or a delegating wrapper that does) is clean by the
        same reference check."""
        if not self._in_package:
            return
        priced = []
        has_record = False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                name = _dotted(sub.func).rsplit(".", 1)[-1]
                if name in _PRICED_CALLS:
                    priced.append((name, sub))
            ident = None
            if isinstance(sub, ast.Name):
                ident = sub.id
            elif isinstance(sub, ast.Attribute):
                ident = sub.attr
            if ident == "record_prediction":
                has_record = True
        if has_record:
            return
        for name, call in priced:
            if id(call) in self._vsc208_seen:
                continue
            self._vsc208_seen.add(id(call))
            self.emit(
                "VSC208",
                f"`{name}` priced a decision in {node.name!r} with no "
                "record_prediction in scope — the prediction never enters "
                "the cost-audit ledger (telemetry/costaudit.py), so it can "
                "never be checked against reality; record it or annotate "
                "the site",
                call,
            )

    # ------------------------------------------------------------- VSC204
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._check_warn_latch(node)
        self._check_priced_decision(node)
        if node.name in self._handler_names:
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    callee = sub.func
                    name = callee.attr if isinstance(callee, ast.Attribute) else (
                        callee.id if isinstance(callee, ast.Name) else None
                    )
                    if name in _SIGNAL_UNSAFE:
                        self.emit(
                            "VSC204",
                            f"`{name}` called inside signal handler "
                            f"{node.name!r} — handlers must only set flags "
                            "(locks/IO/allocation can deadlock the "
                            "interrupted frame)",
                            sub,
                        )
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    # ------------------------------------------------------------- VSC205
    def _visit_loop(self, node) -> None:
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    visit_For = visit_While = visit_AsyncFor = _visit_loop  # type: ignore[assignment]

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        if self._loop_depth > 0:
            bare = node.type is None
            base = isinstance(node.type, ast.Name) and node.type.id == "BaseException"
            # a handler that binds the exception AND uses it is transporting,
            # not swallowing (e.g. boxing it for re-raise on another thread)
            uses_exc = node.name is not None and any(
                isinstance(sub, ast.Name) and sub.id == node.name
                for sub in ast.walk(node)
            )
            if (bare or base) and not uses_exc and not any(
                isinstance(sub, ast.Raise) for sub in ast.walk(node)
            ):
                self.emit(
                    "VSC205",
                    ("bare `except:`" if bare else "`except BaseException:`")
                    + " inside a loop with no re-raise swallows "
                    "KeyboardInterrupt — catch Exception (or re-raise)",
                    node,
                )
        self.generic_visit(node)


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of an expression ('os.environ.get')."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


# --------------------------------------------------------------- VSC104
def _condition_is_rankish(test: ast.AST) -> bool:
    for sub in ast.walk(test):
        name = None
        if isinstance(sub, ast.Name):
            name = sub.id
        elif isinstance(sub, ast.Attribute):
            name = sub.attr
        if name and name.lower() in _RANK_TOKENS:
            return True
    return False


def rank_divergence_findings(source: str, filename: str = "<source>") -> List[Finding]:
    """VSC104: collective calls syntactically under an ``if``/``while``
    whose condition involves a rank-like value — every rank must reach
    every collective, or the mesh deadlocks at that collective."""
    tree = ast.parse(source)
    lines = source.splitlines()
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.If, ast.While)):
            continue
        if not _condition_is_rankish(node.test):
            continue
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            callee = sub.func
            name = callee.attr if isinstance(callee, ast.Attribute) else (
                callee.id if isinstance(callee, ast.Name) else None
            )
            if name in _COLLECTIVE_CALLS:
                lineno = getattr(sub, "lineno", getattr(node, "lineno", 0))
                disabled = _disabled_codes(lines, lineno)
                if "all" in disabled or "VSC104" in disabled:
                    continue
                findings.append(Finding(
                    CODES["VSC104"],
                    f"collective `{name}` is executed only under a "
                    "rank-dependent condition (line "
                    f"{getattr(node, 'lineno', '?')}); ranks that skip it "
                    "deadlock the ones that reach it",
                    where=f"{filename}:{lineno}",
                ))
    return findings


# ------------------------------------------------------------ file driver
_SKIP_DIRS = {"__pycache__", ".git", ".venv", "node_modules", "build",
              "dist", ".pytest_cache", "legacy"}


def iter_python_files(paths: Iterable[str]) -> List[str]:
    out: List[str] = []
    for path in paths:
        if os.path.isfile(path) and path.endswith(".py"):
            out.append(path)
            continue
        for root, dirs, files in os.walk(path):
            dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
            for f in sorted(files):
                if f.endswith(".py"):
                    out.append(os.path.join(root, f))
    return out


def _default_registered(name: str) -> bool:
    from . import envreg

    if envreg.is_registered(name):
        return True
    # docstring families: "VESCALE_IO_BACKOFF_" style prefixes are legal
    # when at least one registered var extends them
    return any(v.name.startswith(name) for v in envreg.all_vars())


def lint_source(
    source: str,
    filename: str = "<source>",
    registered=None,
) -> List[Finding]:
    """Lint one source blob; ``registered`` is the name -> bool predicate
    for VSC202 (defaults to the envreg registry with prefix tolerance)."""
    registered = registered or _default_registered
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding(
            CODES["VSC202"],
            f"file does not parse: {e}",
            where=f"{filename}:{getattr(e, 'lineno', 0)}",
        )]
    linter = _Lint(filename, source, registered)
    linter.prepass(tree)
    linter.visit(tree)
    findings = linter.findings
    findings.extend(rank_divergence_findings(source, filename))
    return findings


def lint_paths(paths: Sequence[str], name: str = "vescale-lint") -> FindingReport:
    report = FindingReport(name)
    for path in iter_python_files(paths):
        try:
            with open(path, "r", encoding="utf-8") as f:
                src = f.read()
        except OSError:
            continue
        rel = os.path.relpath(path)
        report.extend(lint_source(src, rel))
    return report
