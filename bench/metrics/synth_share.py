"""Share of the device-busy time in workload synthesis (the interval's
true counts, the oracle's top-k mask, each lane's row): self time of the
sweep program's ops under its ``synth`` scope (metrics/_scopes.py)."""
from . import _scopes


def read(ctx):
    return _scopes.share(ctx, "synth")
