"""Shard-mapped z3 pass's share of the HBM roofline: rows x 25 bytes per
``mesh-dense`` request (``mesh_roofline.py``, ``rows`` from its
``mesh-scan`` span) over 819 GB/s, divided by ``jit__mesh_scan_mask``'s
device time summed over the chips. Bound: HBM bandwidth."""

import mesh_roofline


def read(run):
    nbytes = sum(mesh_roofline.scan_bytes(a["rows"])
                 for a in mesh_roofline.scans(run))
    return mesh_roofline.share(run, mesh_roofline.SCAN_KERNEL, nbytes)
