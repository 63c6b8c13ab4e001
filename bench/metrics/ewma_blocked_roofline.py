"""Share of its HBM roofline of the dual-EWMA + score kernel
(``ewma_update_kernel``) where a row takes several page blocks and
streams from HBM: one call per ARMS policy pass over the cell's lanes."""
from . import _kernels


def work(B, n):
    """Dual EWMA and score of [B, n] rows: read the two EWMAs and the
    counts (f32) and write the two EWMAs and the score, once each; three
    operations for each EWMA and three for the score, per page."""
    return B * n * 9, B * n * 6 * 4


def read(ctx):
    c = ctx["cell"]
    return _kernels.share(ctx, "ewma_update_kernel", work(c.lanes, c.n))
