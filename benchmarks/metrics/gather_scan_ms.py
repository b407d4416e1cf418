"""Gathered tier, the host's view: mean ``gather-scan`` span time over the
requests that ran one (pad, candidate upload, ``jit__gather_scan_mask``,
mask download; host clock). Beside the kernel's device time in the
breakdown, the difference is the host work around the kernel."""


def read(run):
    per = []
    for t in run.spans:
        ms = [s["duration_ms"] for s in t if s["kind"] == "gather-scan"]
        if ms:
            per.append(sum(ms))
    return sum(per) / len(per) if per else None
