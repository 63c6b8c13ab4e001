"""Share of the device-busy time spent in the Pallas interval kernels."""
from ._kernels import named

KERNELS = ("topk_mask_kernel", "interval_account_kernel",
           "ewma_update_kernel", "tier_migrate_kernel")


def read(ctx):
    tr = ctx["trace"]
    busy = tr.busy_s()
    if busy <= 0:
        return None
    preds = [named(k) for k in KERNELS]
    return 100.0 * tr.select_s(lambda nm: any(p(nm) for p in preds)) / busy
