"""Output tokens produced inside the window over the window's seconds."""


def read(run):
    n = sum(1 for r in run.requests for t in r.token_times
            if run.in_window(t))
    return n / (run.t1 - run.t0) if run.t1 > run.t0 else None
