"""90th percentile of the intervals between successive step completions in
the window, in milliseconds.  The cells' windows hold 100 intervals or more,
ten or more beyond it; under 50 (a window cut short by hand) nothing is
reported."""
import numpy as np

MIN_INTERVALS = 50


def read(ctx):
    w = ctx.window
    if not w or len(w["completions"]) < MIN_INTERVALS:
        return None
    times = np.asarray([w["t0"]] + list(w["completions"]), np.float64)
    return float(np.percentile(np.diff(times) * 1e3, 90))
