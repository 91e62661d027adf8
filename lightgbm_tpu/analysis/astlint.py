"""Tier A of jaxlint: AST-level JAX-specific lint over the package.

Five rules, each targeting a structural failure mode that has cost this
repo real measured performance before (PERF.md rounds 2/4/7) and that
the GPU tree-boosting literature names as the difference between
"on the accelerator" and "fast on the accelerator" (Wen et al.,
Mitchell & Frank: keep the hot loop free of host syncs, retraces and
dtype surprises):

JL001  host sync in a hot path — ``.item()``, ``float()``/``int()``/
       ``bool()``/``np.asarray()`` applied to a device-producing
       expression inside the training/serving hot modules, or
       ``jax.device_get``/``.block_until_ready()`` inside a Python
       loop.  Each one is a device round-trip serialized into the
       iteration.
JL002  retrace hazard — ``jax.jit``/``Partial`` constructed inside a
       loop or invoked immediately (``jax.jit(f)(x)`` compiles per
       call), and calls that pass unhashable (list/dict/set) literals
       for a known jitted symbol's static args.
JL003  dtype-promotion leak — explicit float64 dtypes in ``jnp`` calls
       or ``.astype`` on device values outside a lexical
       ``jax.enable_x64(True)`` block.  Off-TPU this silently
       doubles bandwidth; on TPU it breaks lowering.
JL004  while-carry growth — ``lax.fori_loop``/``while_loop``/``scan``
       whose carry is built by a comprehension/``[x] * n``/starred
       tuple, so the carry arity depends on a Python value (each extra
       carry element is a body-level fusion per split; see
       ops/histogram.py's single stacked carry).
JL005  rank-divergent collective — a ``lax.p*``/``network.global_*``
       collective lexically under a rank-conditional branch in
       ``parallel/``: ranks disagree on whether they enter the
       collective and the job deadlocks.

Findings are keyed ``RULE:path:qualname`` and counted, so the
committed ``jaxlint_baseline.json`` ratchet is stable under line moves;
intentional single syncs carry a ``# jaxlint: ok=JL001`` pragma with a
justifying comment instead of a baseline entry.
"""

from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

RULES: Dict[str, str] = {
    "JL001": "host sync in a hot path",
    "JL002": "retrace hazard",
    "JL003": "dtype-promotion leak outside enable_x64",
    "JL004": "while-carry arity depends on a Python value",
    "JL005": "rank-divergent collective",
}

# Per-rule module scopes, matched against the path relative to the
# package root (``lightgbm_tpu/``).  JL001 covers the modules whose
# loops run per split / per iteration / per serving call; JL003 covers
# the modules that stage device programs; JL005 the collective layer.
JL001_SCOPE = ("ops/", "models/learner.py", "models/serving.py",
               "models/boosting.py", "models/metric.py", "continual/",
               "obs/regress.py", "dataset.py")
JL003_SCOPE = ("ops/", "models/learner.py", "models/serving.py",
               "models/shap.py")
JL005_SCOPE = ("parallel/",)

_DEVICE_ROOTS = ("jnp.", "jax.numpy.", "jax.lax.", "jax.random.",
                 "jax.nn.", "lax.")
_F64_NAMES = {"np.float64", "numpy.float64", "jnp.float64",
              "jax.numpy.float64"}
_COLLECTIVE_ATTRS = {"psum", "pmax", "pmin", "pmean", "all_gather",
                     "all_to_all", "ppermute", "pgather",
                     "process_allgather"}
_RANK_TOKENS = {"rank", "machine_rank", "is_master", "is_rank0",
                "process_index", "axis_index"}

_PRAGMA_RE = re.compile(
    r"#\s*jaxlint:\s*(?:ok|disable)(?:\s*=\s*([A-Z0-9,\s]+))?")


@dataclass
class Finding:
    rule: str
    path: str          # repo-relative posix path
    line: int
    col: int
    func: str          # enclosing function qualname or "<module>"
    message: str

    @property
    def key(self) -> str:
        return f"{self.rule}:{self.path}:{self.func}"

    def to_json(self) -> str:
        return json.dumps({
            "tier": "A", "rule": self.rule, "title": RULES[self.rule],
            "path": self.path, "line": self.line, "col": self.col,
            "func": self.func, "message": self.message, "key": self.key,
        }, sort_keys=True)

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"{self.message}  [{self.func}]")


def _dotted(node: ast.AST) -> Optional[str]:
    """``jax.lax.fori_loop``-style dotted name of a Name/Attribute
    chain, or None for anything more dynamic."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _contains_device_call(node: ast.AST) -> bool:
    """True when the expression subtree contains an explicit
    device-producing call (``jnp.*``/``jax.lax.*``/...).  Names bound
    earlier from such calls are deliberately NOT traced — the rule is a
    high-signal subset, not an escape-proof dataflow analysis."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            d = _dotted(sub.func)
            if d and (d.startswith(_DEVICE_ROOTS) or d + "." in
                      _DEVICE_ROOTS):
                return True
    return False


def _is_f64_token(node: ast.AST) -> bool:
    d = _dotted(node)
    if d in _F64_NAMES:
        return True
    return (isinstance(node, ast.Constant)
            and node.value in ("float64", "double"))


def _rank_conditional(test: ast.AST) -> bool:
    for sub in ast.walk(test):
        if isinstance(sub, ast.Name) and sub.id in _RANK_TOKENS:
            return True
        if isinstance(sub, ast.Attribute) and sub.attr in _RANK_TOKENS:
            return True
        if isinstance(sub, ast.Call):
            d = _dotted(sub.func)
            if d and d.split(".")[-1] in _RANK_TOKENS:
                return True
    return False


def _pragmas(source: str) -> Dict[int, Optional[Set[str]]]:
    """{lineno: suppressed-rule-set or None for all} from
    ``# jaxlint: ok[=JL001,JL003]`` comments."""
    out: Dict[int, Optional[Set[str]]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _PRAGMA_RE.search(line)
        if m:
            rules = m.group(1)
            out[i] = (set(r.strip() for r in rules.split(","))
                      if rules else None)
    return out


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, rel: str, source: str):
        self.path = path          # repo-relative, reported
        self.rel = rel            # package-relative, scope-matched
        self.pragmas = _pragmas(source)
        self.findings: List[Finding] = []
        self.func_stack: List[str] = []
        self.loop_depth = 0
        self.x64_depth = 0
        # jitted symbols with static args seen in this module:
        # name -> set of static argnames (JL002 unhashable-static check)
        self.static_args: Dict[str, Set[str]] = {}

    # -- plumbing -------------------------------------------------------
    def _in(self, scope: Sequence[str]) -> bool:
        return self.rel.startswith(tuple(scope))

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        line = getattr(node, "lineno", 0)
        sup = self.pragmas.get(line)
        if line in self.pragmas and (sup is None or rule in sup):
            return
        self.findings.append(Finding(
            rule=rule, path=self.path, line=line,
            col=getattr(node, "col_offset", 0),
            func=".".join(self.func_stack) or "<module>",
            message=message))

    def visit_FunctionDef(self, node):
        # decorator form of a static-arg jit:
        # @functools.partial(jax.jit, static_argnames=(...))
        for dec in node.decorator_list:
            self._record_static_jit(dec, [ast.Name(id=node.name)])
        self.func_stack.append(node.name)
        saved = self.loop_depth
        self.loop_depth = 0       # a new function body is a new frame
        self.generic_visit(node)
        self.loop_depth = saved
        self.func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node):
        self.func_stack.append(node.name)
        self.generic_visit(node)
        self.func_stack.pop()

    def visit_For(self, node):
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_AsyncFor = visit_For
    visit_While = visit_For

    def visit_With(self, node):
        x64 = any(
            isinstance(item.context_expr, ast.Call)
            and (_dotted(item.context_expr.func) or "").endswith(
                "enable_x64")
            for item in node.items)
        if x64:
            self.x64_depth += 1
        self.generic_visit(node)
        if x64:
            self.x64_depth -= 1

    def visit_Assign(self, node):
        self._record_static_jit(node.value, node.targets)
        self.generic_visit(node)

    def _record_static_jit(self, value: ast.AST, targets) -> None:
        """Track ``name = jax.jit(fn, static_argnames=(...))`` and the
        ``@functools.partial(jax.jit, static_argnames=...)`` decorator
        form so later call sites can be checked for unhashable
        statics."""
        if not isinstance(value, ast.Call):
            return
        d = _dotted(value.func)
        call = value
        if d in ("functools.partial", "partial") and call.args and \
                _dotted(call.args[0]) in ("jax.jit", "jit"):
            pass
        elif d not in ("jax.jit", "jit"):
            return
        names: Set[str] = set()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                for sub in ast.walk(kw.value):
                    if isinstance(sub, ast.Constant) and \
                            isinstance(sub.value, str):
                        names.add(sub.value)
        if not names:
            return
        for t in targets:
            if isinstance(t, ast.Name):
                self.static_args[t.id] = names

    # -- the rules ------------------------------------------------------
    def visit_Call(self, node):
        d = _dotted(node.func)

        # JL001 — host syncs in hot modules
        if self._in(JL001_SCOPE):
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "item" and not node.args:
                self._emit("JL001", node,
                           ".item() forces a device->host sync")
            elif isinstance(node.func, ast.Name) and \
                    node.func.id in ("float", "int", "bool") and \
                    len(node.args) == 1 and \
                    _contains_device_call(node.args[0]):
                self._emit(
                    "JL001", node,
                    f"{node.func.id}() on a device value blocks on a "
                    "device->host sync; keep it on device or batch the "
                    "sync outside the loop")
            elif d in ("np.asarray", "np.array", "numpy.asarray",
                       "numpy.array") and node.args and \
                    _contains_device_call(node.args[0]):
                self._emit(
                    "JL001", node,
                    f"{d}() on a device value is a blocking transfer")
            elif self.loop_depth > 0 and d == "jax.device_get":
                self._emit("JL001", node,
                           "jax.device_get inside a Python loop: one "
                           "transfer per step; batch it")
            elif self.loop_depth > 0 and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "block_until_ready":
                self._emit("JL001", node,
                           "block_until_ready inside a Python loop "
                           "serializes dispatch")

        # JL002 — retrace hazards (whole package)
        if d in ("jax.jit", "jit") or (
                d in ("functools.partial", "partial") and node.args
                and _dotted(node.args[0]) in ("jax.jit", "jit")):
            if self.loop_depth > 0:
                self._emit("JL002", node,
                           "jax.jit constructed inside a loop compiles "
                           "per iteration; hoist and cache it")
        if isinstance(node.func, ast.Call):
            inner = _dotted(node.func.func)
            if inner in ("jax.jit", "jit"):
                self._emit("JL002", node,
                           "jax.jit(f)(x) traces per call; bind the "
                           "jitted callable once")
        if d and d.split(".")[-1] == "Partial" and self.loop_depth > 0:
            self._emit("JL002", node,
                       "Partial built inside a loop defeats jit "
                       "caching (new hashable identity per step)")
        if d in self.static_args:
            for kw in node.keywords:
                if kw.arg in self.static_args[d] and isinstance(
                        kw.value, (ast.List, ast.Dict, ast.Set)):
                    self._emit(
                        "JL002", node,
                        f"unhashable {type(kw.value).__name__.lower()} "
                        f"literal for static arg '{kw.arg}' of jitted "
                        f"'{d}' retraces every call")

        # JL003 — float64 leaks outside enable_x64
        if self._in(JL003_SCOPE) and self.x64_depth == 0:
            if d and d.startswith(("jnp.", "jax.numpy.")):
                for kw in node.keywords:
                    if kw.arg == "dtype" and _is_f64_token(kw.value):
                        self._emit(
                            "JL003", node,
                            f"explicit float64 dtype in {d} outside an "
                            "enable_x64 context")
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "astype" and node.args and \
                    _is_f64_token(node.args[0]) and \
                    _contains_device_call(node.func.value):
                self._emit("JL003", node,
                           ".astype(float64) on a device value outside "
                           "an enable_x64 context")

        # JL004 — carry arity from a Python value (whole package)
        carry_arg = None
        if d in ("jax.lax.fori_loop", "lax.fori_loop") and \
                len(node.args) >= 4:
            carry_arg = node.args[3]
        elif d in ("jax.lax.while_loop", "lax.while_loop") and \
                len(node.args) >= 3:
            carry_arg = node.args[2]
        elif d in ("jax.lax.scan", "lax.scan"):
            if len(node.args) >= 2:
                carry_arg = node.args[1]
            for kw in node.keywords:
                if kw.arg == "init":
                    carry_arg = kw.value
        if carry_arg is not None and self._carry_is_dynamic(carry_arg):
            self._emit(
                "JL004", node,
                "loop carry built from a Python-sized comprehension/"
                "repetition: carry arity tracks a Python value (one "
                "body-level fusion per extra element; stack into one "
                "array instead)")

        # JL005 — collectives under rank conditionals in parallel/
        if self._in(JL005_SCOPE) and d:
            last = d.split(".")[-1]
            if (last in _COLLECTIVE_ATTRS
                    or last.startswith("global_")) and \
                    self._under_rank_branch(node):
                self._emit(
                    "JL005", node,
                    f"collective '{d}' under a rank-conditional "
                    "branch: ranks disagree on entering it and the "
                    "job deadlocks")

        self.generic_visit(node)

    @staticmethod
    def _carry_is_dynamic(node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, (ast.ListComp, ast.GeneratorExp,
                                ast.SetComp, ast.DictComp, ast.Starred)):
                return True
            if isinstance(sub, ast.BinOp) and \
                    isinstance(sub.op, ast.Mult) and (
                    isinstance(sub.left, (ast.List, ast.Tuple))
                    or isinstance(sub.right, (ast.List, ast.Tuple))):
                return True
        return False

    # rank-branch tracking: a stack of If nodes maintained by visit_If
    _rank_if_depth = 0

    def visit_If(self, node):
        self.visit(node.test)
        divergent = _rank_conditional(node.test)
        if divergent:
            self._rank_if_depth += 1
        # BOTH arms are rank-divergent regions: `else:` is entered by
        # exactly the complementary set of ranks
        for child in node.body:
            self.visit(child)
        for child in node.orelse:
            self.visit(child)
        if divergent:
            self._rank_if_depth -= 1

    def _under_rank_branch(self, node) -> bool:
        return self._rank_if_depth > 0


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def lint_source(source: str, path: str,
                package_root: str = "lightgbm_tpu") -> List[Finding]:
    """Lint one module's source.  ``path`` is the repo-relative posix
    path used for scoping and reporting (e.g.
    ``lightgbm_tpu/ops/histogram.py``)."""
    rel = path
    prefix = package_root.rstrip("/") + "/"
    if rel.startswith(prefix):
        rel = rel[len(prefix):]
    tree = ast.parse(source, filename=path)
    linter = _Linter(path, rel, source)
    linter.visit(tree)
    return linter.findings


def iter_package_files(repo_root: str,
                       package: str = "lightgbm_tpu") -> Iterable[str]:
    base = os.path.join(repo_root, package)
    for dirpath, dirnames, filenames in os.walk(base):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def lint_tree(repo_root: str,
              package: str = "lightgbm_tpu") -> List[Finding]:
    findings: List[Finding] = []
    for full in iter_package_files(repo_root, package):
        rel = os.path.relpath(full, repo_root).replace(os.sep, "/")
        with open(full, encoding="utf-8") as fh:
            src = fh.read()
        findings.extend(lint_source(src, rel, package_root=package))
    return findings


def finding_counts(findings: Iterable[Finding]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for f in findings:
        out[f.key] = out.get(f.key, 0) + 1
    return dict(sorted(out.items()))
