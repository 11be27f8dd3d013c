"""Bytes of logits copied from the device to the host per `Engine.step`,
in MB (1e6 B): the ``bytes`` of the window's ``engine.logits_d2h`` spans
(the logits in their own dtype) over its ``engine.step`` spans."""


def read(run):
    spans = run.spans or ()
    d2h = [s["bytes"] for s in spans if s["name"] == "engine.logits_d2h"]
    steps = sum(1 for s in spans if s["name"] == "engine.step")
    return 1e-6 * sum(d2h) / steps if d2h and steps else None
