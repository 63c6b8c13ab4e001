"""Share of the device-busy time in accounting (the interval's cost
model, statistics and tier use): self time of the sweep program's ops
under its ``account`` scope (metrics/_scopes.py)."""
from . import _scopes


def read(ctx):
    return _scopes.share(ctx, "account")
