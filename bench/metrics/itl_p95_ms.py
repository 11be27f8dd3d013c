"""95th percentile, in ms, of every gap between consecutive output tokens
of every request, over the gaps that end inside the window."""

import numpy as np


def read(run):
    gaps = [b - a for r in run.requests
            for a, b in zip(r.token_times, r.token_times[1:])
            if run.in_window(b)]
    return float(np.percentile(gaps, 95) * 1e3) if gaps else None
