"""Share of its roofline of the hop-chain migration kernel
(``tier_migrate_kernel``), one call per policy pass of a single-family binary
panel; P and D are the plans' widths (ARMS: bs_max each)."""
from . import _kernels, _work


def read(ctx):
    c = ctx["cell"]
    knobs = c.policies[0]["knobs"]
    width = int(knobs.get("bs_max", knobs.get("migration_limit", 1)))
    return _kernels.share(ctx, "tier_migrate_kernel", _work.tier_migrate(
        c.lanes, c.n, _kernels.tiers(c), width, width))
