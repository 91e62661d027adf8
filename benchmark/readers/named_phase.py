"""Device seconds per traced iteration of `phases`, as device_phase.py
reads them, where the window's programs name at least one of those phases;
nothing where none does (a commit before the phase existed), so that a
phase that is not there reads as absent and not as 0 s."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import device_phase  # noqa: E402


def read(ctx, programs, phases):
    named = device_phase.phase_by_name(device_phase.scope_tables(programs))
    if not set(named.values()) & set(phases):
        return None
    return device_phase.read(ctx, "s_per_iter", programs, phases)
