"""Counts and seconds of JAX's own compile events, as run.py's CompileLog
gathered them.  `field` is `in_window` (a count) or `before_window_s`."""


def read(ctx, field):
    return ctx["compile"].get(field)
