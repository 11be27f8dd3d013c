"""Share (%) of its roofline that the dtANS SpMM kernel of the compressed
head reaches: the least time the chip could take for the window's calls,
max(operations / bf16 peak, bytes / HBM bandwidth), over the device time of
the kernel's events in the trace.

Operations: 2 * nnz * B (one multiply-add per stored weight and column).
Bytes: the head's encoded size as the run built it, plus x (d_in x B) and
y (d_out x B) in float32.  At these sizes the bytes bound it."""

from bench import spec

#: Name of the kernel's operation on the device: the custom call that
#: `kernels/dtans_spmv.py`'s jitted `dtans_spmm_pallas` lowers to.
KERNEL = "dtans_spmm_pallas"


def bound_s(head: dict, peaks: dict) -> float:
    """Roofline time of one call."""
    b = head["batch"]
    ops = 2.0 * head["nnz"] * b
    nbytes = head["bytes"] + 4.0 * b * (head["d_in"] + head["d_out"])
    return max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    t = run.trace
    if t is None:
        return None
    names = [n for n in t["op_s"] if KERNEL in n]
    secs = sum(t["op_s"][n] for n in names)
    calls = sum(t["op_calls"][n] for n in names)
    if not names or secs <= 0:
        return None
    return 100.0 * calls * bound_s(run.head, spec.peaks_of(run.device_kind)) \
        / secs
