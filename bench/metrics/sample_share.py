"""Share of the device-busy time in sampling (each lane's observed
counts): self time of the sweep program's ops under its ``sample``
scope (metrics/_scopes.py)."""
from . import _scopes


def read(ctx):
    return _scopes.share(ctx, "sample")
