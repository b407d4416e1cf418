"""z3 scan kernels' share of the HBM roofline: the bytes the dense and
gathered kernels must move for the window's queries (``roofline.py``, from
the table's rows and each query's candidate count as the plan explains it)
over 819 GB/s, divided by those kernels' device time in the trace. Bound:
HBM bandwidth."""

import roofline


def read(run):
    if run.trace is None:
        return None
    nbytes = 0
    for r in run.records:
        if r.get("tier") == "dense":
            nbytes += roofline.zscan_dense_bytes(run.rows)
        elif r.get("tier") == "gathered":
            nbytes += roofline.zscan_gathered_bytes(r["candidates"])
    secs = sum(run.trace.kernel_s(names=roofline.ZSCAN_KERNELS).values())
    return roofline.share(nbytes, secs, run.peak)
