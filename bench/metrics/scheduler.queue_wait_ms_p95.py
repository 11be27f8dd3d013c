"""95th percentile, in ms, of the time a request waited in the engine's
queue: ``queued_s`` of the window's ``engine.prefill`` spans, from
`Engine.submit` to the request's admission into a slot."""

import numpy as np


def read(run):
    waits = [s["queued_s"] for s in run.spans or ()
             if s["name"] == "engine.prefill"
             and s.get("queued_s") is not None]
    return float(np.percentile(waits, 95) * 1e3) if waits else None
