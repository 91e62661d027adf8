"""Device seconds of the training step's phases: the traced window's seconds
per operation (`ctx["trace"]["op_seconds"]`, keyed by instruction name) joined
with the program's own scope table (`lightgbm_tpu.obs.scopes.scope_table()`:
{program: {instruction name: phase or None}}, from the HLO text of the
executables the window ran).

An operation counts for a phase when every listed program that has an
instruction of its name gives it that one phase.  A name with no phase, a name
that two programs give to different phases (a trace does not say which program
an operation belongs to) and a name no program has are unattributed.  A
program without a scope table (a commit before it, or a path that registers
nothing) reads 0 s in every phase and 100% unattributed.

`what` is `s_per_iter` (seconds of `phases` per traced iteration), `roofline`
(the least time to read each split parent's rows once, as kernel_roofline.py
reckons it from the trees, over the seconds of `phases`; 0 where they took no
time) or `unattributed_share` (unattributed seconds over the seconds of all
the window's operations, in %: the window's busy seconds where operations do
not overlap, as on one TPU core)."""


def scope_tables(programs):
    """The scope tables of `programs`, those the process has."""
    try:
        from lightgbm_tpu.obs import scopes
    except ImportError:
        return []
    tables = scopes.scope_table()
    return [tables[p] for p in programs if p in tables]


def phase_by_name(tables):
    """{instruction name: phase} of the names whose phase is the same,
    and not None, in every table that has them."""
    seen = {}
    for table in tables:
        for name, phase in table.items():
            seen.setdefault(name, set()).add(phase)
    return {name: next(iter(phases)) for name, phases in seen.items()
            if len(phases) == 1 and None not in phases}


def attribute(op_seconds, by_name):
    """({phase: seconds}, unattributed seconds) of a window's operations."""
    per_phase, unattributed = {}, 0.0
    for name, secs in op_seconds.items():
        phase = by_name.get(name)
        if phase is None:
            unattributed += secs
        else:
            per_phase[phase] = per_phase.get(phase, 0.0) + secs
    return per_phase, unattributed


def read(ctx, what, programs, phases=()):
    trace = ctx["trace"]
    if trace is None:
        return None
    per_phase, unattributed = attribute(
        trace["op_seconds"], phase_by_name(scope_tables(programs)))
    secs = sum(per_phase.get(p, 0.0) for p in phases)
    if what == "s_per_iter":
        return secs / max(1, len(ctx["traced_trees"]))
    if what == "roofline":
        rows_read = sum(int(t.internal_count.sum())
                        for t in ctx["traced_trees"])
        row_bytes = ctx["features"] * ctx["bin_bytes"] + 8
        least = rows_read * row_bytes / (
            ctx["peak"]["hbm_bytes_per_s"] * ctx["chips"])
        return 100.0 * least / secs if secs > 0 else 0.0
    if what == "unattributed_share":
        return 100.0 * unattributed / sum(trace["op_seconds"].values())
    raise ValueError(f"device_phase: unknown what={what!r}")
