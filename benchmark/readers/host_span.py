"""Seconds of one of the harness's own host-clock spans around a call into
the program (`construct`, `generate`, `booster`, `warm_1`, `warm_2`)."""


def read(ctx, span):
    return ctx["spans"].get(span)
