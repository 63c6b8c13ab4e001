"""Share of its HBM roofline of the oracle top-k mask kernel
(``topk_mask_kernel``) where a row takes several page blocks and streams
from HBM: one call per interval over the [W, n] synthesized rows, the
work of ``_work.topk_mask`` (what the algorithm needs, whatever
implements it; the bisection's re-reads of the row are not counted)."""
from . import _kernels, _work


def read(ctx):
    c = ctx["cell"]
    return _kernels.share(ctx, "topk_mask_kernel",
                          _work.topk_mask(len(c.workloads), c.n))
