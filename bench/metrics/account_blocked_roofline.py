"""Share of its HBM roofline of the fused accounting kernel
(``interval_account_kernel``) where a row takes several page blocks and
streams from HBM: one call per interval over the cell's lanes."""
from . import _kernels


def work(B, n, R):
    """Per-tier access split, interval cost and oracle recall of [B, n]
    rows: read the true counts (f32), the tier index (i32) and the oracle
    mask (one byte) once; a compare and an add per page for each tier's
    sum and for the hits."""
    return B * n * 2 * R, B * n * (4 + 4 + 1)


def read(ctx):
    c = ctx["cell"]
    return _kernels.share(ctx, "interval_account_kernel",
                          work(c.lanes, c.n, _kernels.tiers(c)))
