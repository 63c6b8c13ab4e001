"""A Pallas kernel's share of its roofline, from its events in the trace:
calls x least time per call, over the calls' device time."""
from __future__ import annotations

import re

from . import _work


def named(kernel: str):
    """Ops of this kernel: a Pallas call shows in a TPU trace as an
    instruction named after its wrapper, decorated when it is batched
    (``%tier_migrate_kernel.2 =``, ``%vmap_jit_ewma_update_kernel__.2 =``)."""
    pat = re.compile(rf"^%\w*{kernel}\w*(\.\d+)? = ")
    return lambda name: pat.match(name) is not None


def events(trace, kernel: str):
    pred = named(kernel)
    return trace.count(pred), trace.select_s(pred)


def share(ctx, kernel: str, work) -> float | None:
    calls, secs = events(ctx["trace"], kernel)
    if not calls or secs <= 0:
        return None
    ops, nbytes = work
    return 100.0 * calls * _work.roofline_s(ops, nbytes, ctx["peaks"]) / secs


def tiers(cell) -> int:
    return max(len(cell.config["machines"][m]["lat_ns"])
               for m in cell.machines)
