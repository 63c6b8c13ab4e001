"""Share of the device-busy time in the policies (observe, the fire
test, the migration plan, the mode): self time of the sweep program's
ops under its ``policy`` scope (metrics/_scopes.py)."""
from . import _scopes


def read(ctx):
    return _scopes.share(ctx, "policy")
