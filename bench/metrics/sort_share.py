"""Share of the device-busy time spent in XLA sort operations (the
policies' rankings and the workloads' permutation redraws)."""
from trace_reduce import opcode


def read(ctx):
    tr = ctx["trace"]
    busy = tr.busy_s()
    if busy <= 0:
        return None
    return 100.0 * tr.select_s(lambda nm: opcode(nm) == "sort") / busy
