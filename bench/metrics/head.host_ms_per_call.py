"""Host time of one compressed-head call, in ms: the ``serving.sparse_apply``
span (`SparseLinear.apply` through `ops.spmm`'s dispatch, including the
upload of the packed matrix), averaged over the window's calls."""


def read(run):
    d = [s["dur_s"] for s in run.spans or ()
         if s["name"] == "serving.sparse_apply"]
    return 1e3 * sum(d) / len(d) if d else None
