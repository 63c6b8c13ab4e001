"""Device-busy microseconds of the sweep program (the scan engine's
``_sim_synth_jit`` module) per lane-interval it ran."""


def read(ctx):
    secs = ctx["trace"].module_s(lambda nm: "_sim_synth_jit" in nm)
    if secs <= 0:
        return None
    return 1e6 * secs / ctx["lane_intervals"]
