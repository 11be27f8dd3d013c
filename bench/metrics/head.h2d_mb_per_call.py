"""Bytes copied from the host to the device per compressed-head call, in
MB (1e6 B): the ``bytes`` of the window's ``kernels.upload`` spans (the
pack's kernel operands that `ops.packed_arrays` moves to the device) over
its ``serving.sparse_apply`` spans.  A program that uploads nothing keeps
the span with 0 bytes and reads 0.0; one without the span reads nothing."""


def read(run):
    spans = run.spans or ()
    up = [s["bytes"] for s in spans if s["name"] == "kernels.upload"]
    calls = sum(1 for s in spans if s["name"] == "serving.sparse_apply")
    return 1e-6 * sum(up) / calls if up and calls else None
