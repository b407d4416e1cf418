"""Device residual's share of the HBM roofline: the attribute-column bytes
the dense residual must read for the queries that ran it (``roofline.py``)
over 819 GB/s, divided by the device time of every kernel other than the
z3 scan inside those queries' harness annotations. Bound: HBM bandwidth."""

import roofline


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.annotations("bench.query")
    within, nbytes = [], 0
    for r in run.records:
        if r.get("device_residual") and str(r["seq"]) in spans:
            within.append(spans[str(r["seq"])])
            nbytes += roofline.residual_bytes(run.rows, r["pred_types"])
    if not within:
        return None
    secs = sum(v for k, v in run.trace.kernel_s(within=within).items()
               if k not in roofline.ZSCAN_KERNELS)
    return roofline.share(nbytes, secs, run.peak)
