"""Per-layer metric readers, one module per metric of ``BENCHMARK.json``.

Each module's ``read(ctx)`` returns the metric's value, or None where
its cell gives it nothing to read.  ``ctx`` holds ``cell`` (cell.Cell),
``trace`` (trace_reduce.Trace of the window), ``peaks`` (the chip's row
of peaks.json), ``sweeps``, ``lane_intervals`` (lanes x intervals of the
traced window's sweeps) and ``dispatches``.
"""
