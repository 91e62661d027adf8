"""Names for the device's work: the ``lgbm.<phase>`` scope vocabulary of the
training step, and the table that maps a compiled program's instruction
names back to those phases.

A device trace names an operation by its HLO instruction (``fusion.591``)
and carries none of the instruction's metadata, so the scope an operation
was issued under can only come from the compiled program's own HLO text.
The program keeps that text reachable here, without the booster:

* the code that issues device work wraps it in ``jax.named_scope`` through
  :func:`phase` (a decorator) or :func:`scope` (a context manager); scopes
  add metadata only, no operation;
* the boosting loop hands :func:`register` the executable a jitted step's
  first call made (``fn.lower(*args).compile()`` after the call finds that
  executable and compiles nothing) under a fixed program name;
* :func:`scope_table` parses the retained executables' HLO text on first
  request: ``{program: {instruction name: phase or None}}``.

The registry holds compiled executables only: no booster, learner, dataset
or device buffer.  A program name maps to one executable, so a new booster
replaces the previous one's entries and the registry stays at three.
"""

from __future__ import annotations

import functools
import json
import re
import threading
from typing import Any, Callable, Dict, Optional

__all__ = ["PHASES", "PROGRAMS", "phase", "scope", "phase_of", "register",
           "register_call", "scope_table", "dump_scope_table"]

# one vocabulary: the innermost `lgbm.<phase>` component of an operation's
# op_name names the kind of work, under any kernel_plan()
PHASES = (
    "gradients", "sampling", "quantize",          # row passes before the tree
    "histogram", "hist_state", "search",          # per split
    "partition", "split_mega", "bookkeeping",
    "hist_sync",                                  # collectives of the tree
    "leaf_renew", "score_update",                 # row passes after the tree
    "layout_init", "scores_read",                 # outside the step
)
PROGRAMS = ("train.fused_step", "train.scores_read", "train.layout_init")
# the compiler combines a program's collectives into new instructions
# that carry no op_name; such a one takes its program's phase for them:
# the step's only collectives are the tree's syncs, the other two
# programs are one phase each
_COLLECTIVE_PHASE = {"train.fused_step": "hist_sync",
                     "train.scores_read": "scores_read",
                     "train.layout_init": "layout_init"}

_PREFIX = "lgbm."
_SCOPED = re.compile(r"(?:^|/)lgbm\.([A-Za-z0-9_]+)(?=/|$)")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([^\s,}]+)")
_TO_APPLY = re.compile(r"\bto_apply=%([^\s,}]+)")
_MODULE = re.compile(r"HloModule ([^\s,]+)")
_COLLECTIVE = re.compile(
    r"[\])}] (?:all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\(")


def _scope_name(name: str) -> str:
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; the vocabulary is "
                         f"{', '.join(PHASES)}")
    return _PREFIX + name


def scope(name: str):
    """``with scope("partition"):`` — the operations issued inside carry
    ``lgbm.partition`` in their op_name."""
    import jax
    return jax.named_scope(_scope_name(name))


def phase(name: str) -> Callable:
    """Decorator form of :func:`scope` for a function or method whose whole
    body is one kind of work."""
    scoped = _scope_name(name)

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            import jax
            with jax.named_scope(scoped):
                return fn(*args, **kwargs)
        return inner
    return wrap


def phase_of(hlo_text: str,
             collectives: Optional[str] = None) -> Dict[str, Optional[str]]:
    """``{instruction name: phase}`` of one compiled module's text
    (``Compiled.as_text()``): the innermost ``lgbm.*`` component of each
    instruction's op_name, ``None`` where it has none.  A fusion that the
    compiler left without an op_name takes the one phase the instructions
    of its body carry (parameters and constants aside: constants are shared
    across scopes), if they carry exactly one.  Instructions inside fusion
    bodies and scalar ``to_apply`` regions are left out themselves: they
    never run as operations of their own, so a trace never names them.
    A collective that carries no scope (the compiler's combined
    all-reduce) takes the phase ``collectives``, where one is given."""
    lines = hlo_text.splitlines()
    inner = set()
    for line in lines:
        if " fusion(" in line:
            inner.update(_CALLS.findall(line))
        if "to_apply=" in line and " call(" not in line:
            inner.update(_TO_APPLY.findall(line))
    out: Dict[str, Optional[str]] = {}
    body_phases: Dict[str, set] = {}
    unscoped_fusions = []
    computation = None
    for line in lines:
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        found = _SCOPED.findall(op.group(1)) if op else []
        phase = found[-1] if found else None
        if computation in inner:
            if phase and " constant(" not in line \
                    and " parameter(" not in line:
                body_phases.setdefault(computation, set()).add(phase)
            continue
        if phase is None and collectives and _COLLECTIVE.search(line):
            phase = collectives
        out[m.group(1)] = phase
        if phase is None and " fusion(" in line:
            unscoped_fusions.append((m.group(1), _CALLS.findall(line)))
    for name, called in unscoped_fusions:
        phases = set().union(*(body_phases.get(c, ()) for c in called))
        if len(phases) == 1:
            out[name] = phases.pop()
    return out


# ---------------------------------------------------------------------------
# the registry: program name -> the executable its first call made
# ---------------------------------------------------------------------------
_lock = threading.Lock()
_executables: Dict[str, Any] = {}
_tables: Dict[str, Any] = {}   # program -> (module name, table)


def register(program: str, executable: Any) -> None:
    """Keep ``executable`` (anything with ``hlo_modules()``: what
    ``Compiled.runtime_executable()`` returns) under ``program``, in place
    of the one kept before."""
    if program not in PROGRAMS:
        raise ValueError(f"unknown program {program!r}; the names are "
                         f"{', '.join(PROGRAMS)}")
    with _lock:
        _executables[program] = executable
        _tables.pop(program, None)


def register_call(program: str, jitted, *args) -> None:
    """Register the executable that ``jitted(*args)`` has just run: lowering
    again on the same arguments finds the call's own trace, lowering and
    executable in JAX's caches.  ``args`` must be live (the call's outputs
    stand in for donated inputs of the same shape)."""
    compiled = jitted.lower(*args).compile()
    register(program, compiled.runtime_executable())


def _parsed() -> Dict[str, Any]:
    """{program: (HLO module name, table)} of the registered programs,
    parsed on first request and kept."""
    with _lock:
        for program, exe in _executables.items():
            if program not in _tables:
                text = "\n".join(m.to_string() for m in exe.hlo_modules())
                module = _MODULE.match(text)
                _tables[program] = (module.group(1) if module else "",
                                    phase_of(
                                        text, _COLLECTIVE_PHASE[program]))
        return dict(_tables)


def scope_table() -> Dict[str, Dict[str, Optional[str]]]:
    """``{program: {instruction name: phase or None}}`` of the registered
    programs."""
    return {p: dict(table) for p, (_, table) in _parsed().items()}


def dump_scope_table(path: str) -> None:
    """Write the scope table as JSON for ``tools/trace_report.py device``:
    ``{"modules": {program: HLO module name}, "tables": scope_table()}``.
    A device trace names the module each operation ran in, which tells two
    programs' instructions of one name apart."""
    parsed = _parsed()
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"modules": {p: m for p, (m, _) in parsed.items()},
                   "tables": {p: t for p, (_, t) in parsed.items()}}, f)


def _reset() -> None:
    """Forget every registered program (tests)."""
    with _lock:
        _executables.clear()
        _tables.clear()
