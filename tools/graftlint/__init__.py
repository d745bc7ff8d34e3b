#
# graftlint: AST-level JAX/TPU invariant checks for this codebase.
#
# The reference stack (cuML/NCCL) fails loudly when a worker misuses the
# device; the jax/pjit rebuild fails silently — a stray np.asarray on a
# device array becomes a hidden device->host sync, a Python-scalar jit arg
# becomes a recompile stream, an axis-name typo explodes only at trace time
# on a real mesh.  graftlint moves those failures to review time.  Rules:
#
#   R1 host-sync     np.asarray/.item()/float()/np reductions on values that
#                    dataflow from jnp/jax.lax/jitted calls, inside loops or
#                    jitted bodies; jax.device_get inside loops.
#   R2 recompile     jit-wrapped callables taking shape/config-named params
#                    without static_argnums/static_argnames; Python if/while
#                    on non-static params inside a jitted body.
#   R3 axis-name     lax collectives / PartitionSpec / Mesh axis names given
#                    as string literals instead of names bound through
#                    parallel/mesh (DATA_AXIS/MODEL_AXIS).
#   R4 nondeterminism  legacy np.random global-state calls; unseeded
#                    default_rng(); any RNG call at module scope; iteration
#                    over set values (order feeds collectives/encodings).
#   R5 dtype         float64 dtypes in ops/ solver kernels (TPU demotes f64
#                    to slow emulation; numpy f64 scalars also silently
#                    promote weak-typed jnp math).
#   R6 raw-clock     time.time/time.perf_counter in spark_rapids_ml_tpu
#                    modules outside profiling.py — all timing goes through
#                    srml-scope (profiling.now()/span()) so spans, counters,
#                    and trace exports share one clock.
#   R7 unnamed-thread  threading.Thread/Timer without name= in
#                    spark_rapids_ml_tpu modules — the srml-watch flight
#                    recorder, trace exports, and watchdog reports attribute
#                    events by thread name; "Thread-N" is useless in a hang
#                    dump.
#   R8 remote-dma    pltpu.make_async_remote_copy outside parallel/
#                    exchange.py (the ONE audited home of the inter-chip
#                    DMA surface), and DMA handles .start()ed without a
#                    matching .wait() in the same kernel body — an
#                    unwaited remote copy races the output block's flush
#                    and can wedge the device in FAILED_PRECONDITION.
#   R9 unbounded-wait  .result()/.wait()/.acquire()/.join() with no
#                    timeout, and `except Exception:` bodies with no call
#                    and no raise (silent teardown swallows), in
#                    spark_rapids_ml_tpu/{parallel,serving}/ — the modules
#                    that wait on other processes/threads, where a dead
#                    peer turns an unbounded wait into the srml-shield
#                    motivating failure mode ("hang for 5 minutes, then
#                    die without naming the culprit").
#   R10 raw-socket   socket.socket/create_connection outside parallel/
#                    netplane.py (the ONE audited home of the wire
#                    surface — anywhere else is un-lease-fenced and
#                    un-fault-injectable), and recv/accept inside
#                    netplane without a preceding settimeout in the same
#                    function body (the socket analog of R9).
#   R11 lock-order   whole-program concurrency pass (concurrency.py):
#                    cycles in the package-wide held->acquired lock graph
#                    (lock-order inversions, incl. interprocedural edges
#                    through same-module calls), and blocking operations
#                    performed while a lock is held (socket waits,
#                    Future.result, foreign Condition.wait, compile
#                    waits, device syncs, subprocess/sleep).
#   R12 shared-state instance attributes written both under a lock and
#                    with no lock held, and in-place container mutation
#                    of lock-free attributes, in the thread-spawning
#                    modules (serving/, parallel/, ann/mutable.py,
#                    stream/session.py, watch.py).
#
# Suppression: `# graftlint: disable=R1 (reason)` on the finding line or the
# line directly above; the reason in the parentheses is the audit record.
#
# The runtime counterpart (SRML_SANITIZE=1 transfer guard + NaN checks) lives
# in spark_rapids_ml_tpu/sanitize.py; docs/graftlint.md documents both.
#

from __future__ import annotations

import hashlib
import json
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Set, Tuple, Union

from .concurrency import ParsedModule, lint_concurrency
from .rules import CONCURRENCY_RULES, RULES, ModuleIndex, lint_tree

__all__ = [
    "Finding",
    "lint_source",
    "lint_paths",
    "load_baseline",
    "write_baseline",
    "apply_baseline",
    "assign_ids",
    "RULE_NAMES",
]

RULE_NAMES = {
    "R1": "host-sync",
    "R2": "recompile",
    "R3": "axis-name",
    "R4": "nondeterminism",
    "R5": "dtype",
    "R6": "raw-clock",
    "R7": "unnamed-thread",
    "R8": "remote-dma",
    "R9": "unbounded-wait",
    "R10": "raw-socket",
    "R11": "lock-order",
    "R12": "shared-state",
}

# Findings sanctioned by construction, not by pragma.  Entries are
# "<path-suffix>" (whole file) or "<path-suffix>::<function>".  Keep this
# list SHORT — the point of the dedup work was shrinking it to single sites.
ALLOWLIST: Dict[str, Tuple[str, ...]] = {
    # the ONE sanctioned np.asarray(block.toarray()) ingest materialization
    # (dense/sparse pandas blocks are host data; the dataflow pass would not
    # taint them, but the entry documents the contract and guards a future
    # device-backed block type)
    "R1": ("spark_rapids_ml_tpu/utils.py::materialize_feature_block",),
    # the axis-name binding site itself: DATA_AXIS/MODEL_AXIS are DEFINED
    # here, so its own Mesh/PartitionSpec construction uses the literals
    "R3": ("spark_rapids_ml_tpu/parallel/mesh.py",),
}

_PRAGMA_RE = re.compile(
    r"#\s*graftlint:\s*disable=([A-Za-z0-9_,\s]+?)(?:\s*\(([^)]*)\))?\s*$"
)


@dataclass(frozen=True)
class Finding:
    rule: str  # "R1".."R5"
    path: str
    line: int
    message: str
    func: str = ""  # enclosing function qualname ("" at module scope)

    @property
    def name(self) -> str:
        return RULE_NAMES[self.rule]

    def render(self) -> str:
        where = f"{self.path}:{self.line}"
        return f"{where}: {self.rule}[{self.name}] {self.message}"


def _pragma_rules(line_text: str) -> Optional[set]:
    m = _PRAGMA_RE.search(line_text)
    if not m:
        return None
    return {r.strip() for r in m.group(1).split(",") if r.strip()}


def collect_pragmas(source: str) -> Dict[int, set]:
    """Line number -> set of disabled rules ('all' disables every rule)."""
    out: Dict[int, set] = {}
    for i, text in enumerate(source.splitlines(), start=1):
        rules = _pragma_rules(text)
        if rules:
            out[i] = rules
    return out


def _suppressed(f: Finding, pragmas: Dict[int, set]) -> bool:
    for line in (f.line, f.line - 1):
        rules = pragmas.get(line)
        if rules and (f.rule in rules or "all" in rules):
            return True
    return False


def _allowlisted(f: Finding) -> bool:
    for entry in ALLOWLIST.get(f.rule, ()):
        if "::" in entry:
            suffix, func = entry.split("::", 1)
            if f.path.endswith(suffix) and f.func == func:
                return True
        elif f.path.endswith(entry):
            return True
    return False


def _parse_module(source: str, path: str) -> ParsedModule:
    import ast

    tree = ast.parse(source, filename=path)
    return ParsedModule(path=path, tree=tree, index=ModuleIndex(tree, path))


def _per_module_findings(
    pm: ParsedModule, selected: Set[str]
) -> List[Finding]:
    return [
        Finding(rule=r, path=pm.path, line=line, message=msg, func=func)
        for (r, line, msg, func) in lint_tree(pm.tree, pm.index, selected)
    ]


def _concurrency_findings(
    parsed: List[ParsedModule], selected: Set[str]
) -> List[Finding]:
    if not (selected & set(CONCURRENCY_RULES)):
        return []
    return [
        Finding(rule=r, path=path, line=line, message=msg, func=func)
        for (r, path, line, msg, func) in lint_concurrency(parsed, selected)
    ]


def lint_source(
    source: str, path: str = "<string>", rules: Optional[Iterable[str]] = None
) -> List[Finding]:
    """Lint one module's source; returns unsuppressed findings sorted by
    line.  `rules` restricts to a subset (default: all).  The concurrency
    pass (R11/R12) runs over the single module — interprocedural edges
    stay within it, exactly as in a whole-package run."""
    pm = _parse_module(source, path)
    selected = set(rules) if rules is not None else set(RULES)
    raw = _per_module_findings(pm, selected)
    raw.extend(_concurrency_findings([pm], selected))
    pragmas = collect_pragmas(source)
    return sorted(
        (f for f in raw if not _suppressed(f, pragmas) and not _allowlisted(f)),
        key=lambda f: (f.line, f.rule),
    )


def iter_python_files(paths: Iterable[str]) -> Iterable[str]:
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            yield p
        elif os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(
                    d for d in dirs if d not in ("__pycache__", ".git")
                )
                for fn in sorted(files):
                    if fn.endswith(".py"):
                        yield os.path.join(root, fn)


def lint_paths(
    paths: Iterable[str], rules: Optional[Iterable[str]] = None
) -> List[Finding]:
    """Lint a set of files/packages as ONE program: per-module rules run
    file by file, then the concurrency pass (R11/R12) runs once over every
    parsed module so the lock graph is package-wide.  Pragmas and the
    allowlist apply to both halves."""
    selected = set(rules) if rules is not None else set(RULES)
    parsed: List[ParsedModule] = []
    pragmas_of: Dict[str, Dict[int, set]] = {}
    findings: List[Finding] = []
    for path in iter_python_files(paths):
        with open(path, "r", encoding="utf-8") as f:
            source = f.read()
        norm = os.path.normpath(path)
        pm = _parse_module(source, norm)
        parsed.append(pm)
        pragmas_of[norm] = collect_pragmas(source)
        findings.extend(_per_module_findings(pm, selected))
    findings.extend(_concurrency_findings(parsed, selected))
    return sorted(
        (
            f
            for f in findings
            if not _suppressed(f, pragmas_of.get(f.path, {}))
            and not _allowlisted(f)
        ),
        key=lambda f: (f.path, f.line, f.rule),
    )


# -- stable finding ids -------------------------------------------------------
# A finding's identity is (rule, path, symbol, fingerprint-of-message) — NO
# line numbers, so a baseline survives unrelated edits that shift code up
# or down.  Identical findings in the same symbol (two copies of the same
# bad call) get an occurrence suffix in first-seen order.

def _fingerprint(f: Finding) -> str:
    h = hashlib.sha1(
        f"{f.rule}|{f.path}|{f.func}|{f.message}".encode("utf-8")
    )
    return h.hexdigest()[:10]


def assign_ids(findings: List[Finding]) -> List[Tuple[str, Finding]]:
    """[(stable id, finding)] in (path, line, rule) order."""
    ordered = sorted(findings, key=lambda f: (f.path, f.line, f.rule))
    seen: Dict[str, int] = {}
    out: List[Tuple[str, Finding]] = []
    for f in ordered:
        base = f"{f.rule}:{f.path}::{f.func or '<module>'}@{_fingerprint(f)}"
        n = seen.get(base, 0)
        seen[base] = n + 1
        out.append((base if n == 0 else f"{base}~{n + 1}", f))
    return out


# -- baseline: ratchet the whole-package gate --------------------------------
# v2 (written by --write-baseline, consumed by --fail-on-new): a list of
# stable finding ids — audited debt.  Findings whose id is recorded demote
# to warnings; any NEW id is an error, so the gate only ever ratchets down.
# v1 (legacy): {"<path>::<rule>": count} — per-(file, rule) count budgets.

Baseline = Union[Dict[str, int], Set[str]]


def load_baseline(path: str) -> Baseline:
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    if isinstance(data, dict) and data.get("version") == 2:
        ids = data.get("ids")
        if not isinstance(ids, list):
            raise ValueError(f"baseline {path}: v2 needs an 'ids' list")
        return {str(i) for i in ids}
    if not isinstance(data, dict):
        raise ValueError(f"baseline {path} must be a JSON object")
    return {str(k): int(v) for k, v in data.items()}


def write_baseline(path: str, findings: List[Finding]) -> List[str]:
    ids = [i for i, _f in assign_ids(findings)]
    with open(path, "w", encoding="utf-8") as fh:
        json.dump({"version": 2, "ids": sorted(ids)}, fh, indent=2)
        fh.write("\n")
    return ids


def apply_baseline(
    findings: List[Finding], baseline: Baseline
) -> Tuple[List[Finding], List[Finding]]:
    """Split findings into (errors, warnings).  v2 baselines match by
    stable id (line-number independent); v1 baselines match per (path,
    rule) up to the recorded count."""
    errors: List[Finding] = []
    warnings: List[Finding] = []
    if isinstance(baseline, set):
        for fid, f in assign_ids(findings):
            (warnings if fid in baseline else errors).append(f)
        return errors, warnings
    budget = dict(baseline)
    for f in findings:
        k = f"{f.path}::{f.rule}"
        if budget.get(k, 0) > 0:
            budget[k] -= 1
            warnings.append(f)
        else:
            errors.append(f)
    return errors, warnings
