"""Share of its roofline of the oracle top-k mask kernel
(``topk_mask_kernel``), one call per interval over the [W, n]
synthesized rows."""
from . import _kernels, _work


def read(ctx):
    c = ctx["cell"]
    return _kernels.share(ctx, "topk_mask_kernel",
                          _work.topk_mask(len(c.workloads), c.n))
