"""Share of the device-busy time in the migration executor (the tier
moves and the wasteful-migration count): self time of the sweep
program's ops under its ``migrate`` scope (metrics/_scopes.py)."""
from . import _scopes


def read(ctx):
    return _scopes.share(ctx, "migrate")
