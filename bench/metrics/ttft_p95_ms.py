"""95th percentile of time to first token, in ms, over every request due
in the window of an open-loop run: from the time the request was due (not
when it was submitted) to the host clock after the step that produced its
first token.  A request that never got one counts with the time it waited.
"""

import numpy as np


def read(run):
    if not run.open_loop or not run.due:
        return None
    waits = [(r.token_times[0] if r.token_times else run.t_stop)
             - (run.t_go + r.due) for r in run.due]
    return float(np.percentile(waits, 95) * 1e3)
