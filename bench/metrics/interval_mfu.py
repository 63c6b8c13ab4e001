"""The whole interval's share of the chip's peak: the least time the
chip needs for one lane-interval's work (metrics/_work.lane_interval,
averaged over the panel's families) over the device-busy time of the
sweep program (the ``_sim_synth_jit`` module) per lane-interval.  Host
time between the program's runs is not in it."""
from . import _kernels, _work


def read(ctx):
    secs = ctx["trace"].module_s(lambda nm: "_sim_synth_jit" in nm)
    if secs <= 0:
        return None
    c = ctx["cell"]
    R = _kernels.tiers(c)
    fams = [p["family"] for p in c.policies]
    per = sum(_work.roofline_s(*_work.lane_interval(f, c.n, R),
                               ctx["peaks"]) for f in fams) / len(fams)
    return 100.0 * per * ctx["lane_intervals"] / secs
