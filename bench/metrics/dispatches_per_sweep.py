"""Compiled simulation dispatches per sweep call
(``scan_engine.count_dispatches``)."""


def read(ctx):
    return ctx["dispatches"] / ctx["sweeps"]
