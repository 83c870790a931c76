"""Pass 3 — repo-invariant lint: AST enforcement of rules the codebase
states only in comments.

The rule classes over `src/repro`:

  scheduler-no-jax        serve/scheduler.py promises "Nothing in this
                          module imports JAX" — the Gateway relies on it
                          to unit-test scheduling with scripted fakes
                          and to keep dispatch single-threaded semantics
                          out of the policy layer.
  scheduler-determinism   the round-robin path must be deterministic:
                          no `time.time`/`time.time_ns`, no `random`,
                          `numpy.random`, `secrets`, or `uuid` in
                          serve/scheduler.py (`repro.obs.timer` is the
                          sanctioned clock — it only feeds latency
                          reports, never ordering).
  no-raw-timing           modules under serve/ and query/ must not call
                          `time.perf_counter` (or `perf_counter_ns`,
                          `monotonic`, `monotonic_ns`, `process_time`)
                          directly: latency measured ad hoc never
                          reaches the metrics registry or the trace.
                          `repro.obs` (`timer()`, `Timer`, tracer
                          spans) is the one clock; obs/ itself is the
                          sanctioned home of the raw calls.
  no-tracer-concretize    inside jit-decorated functions and Pallas
                          kernel bodies (`*_body` / `*_kernel`),
                          `.item()`, `int(x)`, `float(x)` on traced
                          values raise ConcretizationTypeError at trace
                          time — or worse, silently constant-fold a
                          weak type.  Static-shape reads
                          (`int(x.shape[0])`, `len(...)`) are allowed.
  label-coverage          every identity/serialization surface that two
                          label variants of one skeleton could alias
                          through must keep referencing the labels
                          field: `canonical_key` + `_wl_cells`
                          (query/canon.py), `Pattern.to_dict` +
                          `_automorphisms_cached` (core/pattern.py),
                          `plan_to_dict` (core/plan.py, vlabels),
                          `fingerprint` (graph/csr.py), and the store's
                          `_record_labeled`.  A refactor that drops
                          labels from any of them would silently merge
                          a labeled pattern with its skeleton — cache
                          aliasing that no runtime check catches —
                          so the lint fails if the function loses its
                          labels reference OR disappears outright.
  no-stale-fingerprint    modules under serve/ and query/ must not stash
                          a graph fingerprint on long-lived object state
                          (`self.fp = graph.fingerprint`, `self._key =
                          graph_fingerprint(...)`): on a live engine the
                          graph mutates between rounds, so a captured
                          fingerprint silently keys new-epoch counts
                          under an old-epoch identity.  Hold an
                          `EpochStamp` (live/epoch.py) instead — it is
                          swapped atomically at round boundaries — and
                          read fingerprints through it at use sites.
                          Locals are fine; only attribute stores
                          (state that survives a round) are flagged.

Pure `ast` — no imports of the linted modules, so a module that fails
to import is still lintable (and a syntax error becomes a finding).
"""
from __future__ import annotations

import ast
from pathlib import Path

from .findings import ERROR, Finding

_NONDETERMINISTIC_MODULES = {"random", "secrets", "uuid"}
_NONDETERMINISTIC_ATTRS = {
    "time.time", "time.time_ns", "numpy.random", "np.random",
    "os.urandom",
}

# raw clocks forbidden outside repro/obs in the serving + query layers
_RAW_TIMING_NAMES = {
    "perf_counter", "perf_counter_ns", "monotonic", "monotonic_ns",
    "process_time", "process_time_ns",
}
_RAW_TIMING_ATTRS = {f"time.{n}" for n in _RAW_TIMING_NAMES}

# label-coverage: (path suffix) -> {function name: required token}.
# Each named function is an identity or serialization surface; losing
# its labels/vlabels reference would alias labeled patterns with their
# unlabeled skeletons somewhere downstream (cache keys, store records,
# graph fingerprints, automorphism groups).
_LABEL_SURFACES: dict[str, dict[str, str]] = {
    "core/pattern.py": {"to_dict": "labels",
                        "_automorphisms_cached": "labels"},
    "query/canon.py": {"canonical_key": "labels", "_wl_cells": "labels"},
    "core/plan.py": {"plan_to_dict": "vlabels"},
    "graph/csr.py": {"fingerprint": "labels"},
    "query/store.py": {"_record_labeled": "vlabels"},
}


def _in_timed_scope(rel: str) -> bool:
    """True for modules under serve/ or query/ (where `no-raw-timing`
    applies), excluding repro/obs — the one sanctioned home of the raw
    clock calls."""
    p = rel.replace("\\", "/")
    if "/obs/" in p or p.startswith("obs/"):
        return False
    return any(f"/{d}/" in p or p.startswith(f"{d}/")
               for d in ("serve", "query"))


def _err(rule: str, loc: str, msg: str) -> Finding:
    return Finding(ERROR, rule, loc, msg)


def _dotted(node: ast.AST) -> str | None:
    """'jax.sharding.set_mesh' for a Name/Attribute chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _has_jit_decorator(fn: ast.FunctionDef | ast.AsyncFunctionDef) -> bool:
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = _dotted(target) or ""
        if name in ("jit", "jax.jit", "pjit", "jax.pjit"):
            return True
        # @partial(jax.jit, ...) / @functools.partial(jit, ...)
        if isinstance(dec, ast.Call) and name.split(".")[-1] == "partial":
            for arg in dec.args[:1]:
                inner = _dotted(arg) or ""
                if inner in ("jit", "jax.jit", "pjit", "jax.pjit"):
                    return True
    return False


def _is_static_shape_read(arg: ast.AST) -> bool:
    """int(x.shape[0]) / float(len(xs)) / int(x.ndim) are trace-safe."""
    for node in ast.walk(arg):
        if isinstance(node, ast.Attribute) and node.attr in (
                "shape", "ndim", "size", "dtype"):
            return True
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id == "len":
            return True
    return False


def _check_traced_body(fn, rel: str) -> list[Finding]:
    out: list[Finding] = []
    for node in ast.walk(fn):
        loc = f"{rel}:{getattr(node, 'lineno', fn.lineno)}"
        if isinstance(node, ast.Call):
            callee = node.func
            if isinstance(callee, ast.Attribute) and callee.attr == "item":
                out.append(_err(
                    "no-tracer-concretize", loc,
                    f".item() inside traced function {fn.name!r} forces a "
                    f"device sync / concretization at trace time"))
            elif isinstance(callee, ast.Name) and callee.id in (
                    "int", "float") and node.args:
                arg = node.args[0]
                if not isinstance(arg, ast.Constant) \
                        and not _is_static_shape_read(arg):
                    out.append(_err(
                        "no-tracer-concretize", loc,
                        f"{callee.id}() on a (potentially traced) value "
                        f"inside {fn.name!r}; only static shape reads are "
                        f"trace-safe"))
    return out


def _mentions_fingerprint(node: ast.AST) -> bool:
    """Does this expression read a `.fingerprint` attribute (property or
    method) or call/reference `graph_fingerprint`?"""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr == "fingerprint":
            return True
        if isinstance(sub, ast.Name) and sub.id == "graph_fingerprint":
            return True
    return False


def _check_stale_fingerprint(node, rel: str) -> list[Finding]:
    """no-stale-fingerprint: an attribute store in serve/query whose
    value derives from a fingerprint captures graph identity on state
    that outlives the round — stale the moment a live engine mutates."""
    targets = node.targets if isinstance(node, ast.Assign) else [node.target]
    if not any(isinstance(sub, ast.Attribute)
               for t in targets for sub in ast.walk(t)):
        return []
    value = node.value
    if value is None or not _mentions_fingerprint(value):
        return []
    return [_err(
        "no-stale-fingerprint", f"{rel}:{node.lineno}",
        "fingerprint captured on long-lived state in the serve/query "
        "path; on a live engine it goes stale at the next mutation "
        "round — hold an EpochStamp (repro.live.epoch) and read "
        "fingerprints through it at use sites instead")]


def _references_token(fn: ast.AST, token: str) -> bool:
    """Does the function body mention `token` as an attribute, name, or
    string literal (dict key)?"""
    for node in ast.walk(fn):
        if isinstance(node, ast.Attribute) and node.attr == token:
            return True
        if isinstance(node, ast.Name) and node.id == token:
            return True
        if isinstance(node, ast.Constant) and node.value == token:
            return True
        if isinstance(node, ast.keyword) and node.arg == token:
            return True
    return False


def _check_label_surfaces(tree: ast.Module, rel: str,
                          surfaces: dict[str, str]) -> list[Finding]:
    found: set[str] = set()
    out: list[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        token = surfaces.get(node.name)
        if token is None:
            continue
        found.add(node.name)
        if not _references_token(node, token):
            out.append(_err(
                "label-coverage", f"{rel}:{node.lineno}",
                f"{node.name}() no longer references {token!r}: labeled "
                f"patterns would alias their unlabeled skeletons through "
                f"this identity/serialization surface"))
    for name in sorted(set(surfaces) - found):
        out.append(_err(
            "label-coverage", rel,
            f"expected label-carrying function {name}() not found; if it "
            f"was renamed, update _LABEL_SURFACES to keep the labels "
            f"field pinned to the new surface"))
    return out


def lint_source(src: str, rel: str) -> list[Finding]:
    """Lint one module's source; `rel` is the repo-relative path used in
    finding locations and to select per-file rules."""
    try:
        tree = ast.parse(src, filename=rel)
    except SyntaxError as e:
        return [_err("syntax", f"{rel}:{e.lineno or 0}", f"does not parse: {e.msg}")]

    posix = rel.replace("\\", "/")
    is_scheduler = posix.endswith("serve/scheduler.py")
    is_timed = _in_timed_scope(rel)
    out: list[Finding] = []
    for suffix, surfaces in _LABEL_SURFACES.items():
        if posix.endswith(suffix):
            out += _check_label_surfaces(tree, rel, surfaces)

    for node in ast.walk(tree):
        loc = f"{rel}:{getattr(node, 'lineno', 0)}"

        if isinstance(node, ast.Import):
            for alias in node.names:
                root = alias.name.split(".")[0]
                if is_scheduler and root == "jax":
                    out.append(_err(
                        "scheduler-no-jax", loc,
                        f"import {alias.name}: the scheduler is the "
                        f"JAX-free policy layer by contract"))
                if is_scheduler and root in _NONDETERMINISTIC_MODULES:
                    out.append(_err(
                        "scheduler-determinism", loc,
                        f"import {alias.name}: nondeterminism in the "
                        f"round-robin path breaks the tested interleaving"))

        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            root = mod.split(".")[0]
            if is_scheduler and root == "jax":
                out.append(_err(
                    "scheduler-no-jax", loc,
                    f"from {mod} import ...: the scheduler is the "
                    f"JAX-free policy layer by contract"))
            if is_scheduler and root in _NONDETERMINISTIC_MODULES:
                out.append(_err(
                    "scheduler-determinism", loc,
                    f"from {mod} import ...: nondeterminism in the "
                    f"round-robin path"))
            if is_timed and mod == "time":
                for a in node.names:
                    if a.name in _RAW_TIMING_NAMES:
                        out.append(_err(
                            "no-raw-timing", loc,
                            f"from time import {a.name}: raw timing in "
                            f"the serve/query path — use repro.obs "
                            f"(timer()/Timer or a tracer span) so the "
                            f"measurement reaches the metrics registry"))

        elif isinstance(node, ast.Attribute):
            name = _dotted(node)
            if name is None:
                continue
            if is_scheduler and name.split(".")[0] == "jax":
                out.append(_err(
                    "scheduler-no-jax", loc,
                    f"{name}: the scheduler must not touch JAX"))
            if is_scheduler and name in _NONDETERMINISTIC_ATTRS:
                out.append(_err(
                    "scheduler-determinism", loc,
                    f"{name}: nondeterministic call in the round-robin "
                    f"path (repro.obs.timer is the sanctioned clock)"))
            if is_timed and name in _RAW_TIMING_ATTRS:
                out.append(_err(
                    "no-raw-timing", loc,
                    f"{name}: raw timing in the serve/query path — use "
                    f"repro.obs (timer()/Timer or a tracer span) so the "
                    f"measurement reaches the metrics registry"))

        elif isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            if is_timed:
                out += _check_stale_fingerprint(node, rel)

        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if _has_jit_decorator(node) or node.name.endswith(("_body",
                                                              "_kernel")):
                out += _check_traced_body(node, rel)

    return out


def lint_path(path: Path, root: Path) -> list[Finding]:
    rel = str(path.relative_to(root))
    try:
        src = path.read_text()
    except OSError as e:
        return [_err("syntax", rel, f"unreadable: {e}")]
    return lint_source(src, rel)


def lint_tree(root: Path | str) -> list[Finding]:
    """Lint every Python module under `<root>/src/repro` (or `root`
    itself when it already points inside a source tree)."""
    root = Path(root)
    base = root / "src" / "repro"
    if not base.is_dir():
        base = root
    out: list[Finding] = []
    for path in sorted(base.rglob("*.py")):
        out += lint_path(path, root)
    return out
