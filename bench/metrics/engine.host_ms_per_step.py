"""Host time of one `Engine.step` outside admission and decode, in ms: the
``engine.step`` span's duration minus its ``engine.refill`` and
``engine.decode`` children (sampling and bookkeeping), averaged over the
window's steps."""


def read(run):
    if not run.spans:
        return None
    steps = {s["id"]: s["dur_s"] for s in run.spans
             if s["name"] == "engine.step"}
    child = {}
    for s in run.spans:
        if s["name"] in ("engine.refill", "engine.decode") \
                and s["parent"] in steps:
            child[s["parent"]] = child.get(s["parent"], 0.0) + s["dur_s"]
    if not steps:
        return None
    return 1e3 * sum(d - child.get(i, 0.0)
                     for i, d in steps.items()) / len(steps)
