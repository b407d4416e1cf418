"""Mesh hit compaction's share of the HBM roofline: rows x 1 mask byte
read plus cap x 4 index bytes written per ``mesh-dense`` request
(``mesh_roofline.py``, ``rows`` and ``cap`` from its ``mesh-scan`` span)
over 819 GB/s, divided by ``jit__mask_hit_rows``'s device time summed
over the chips. Bound: HBM bandwidth."""

import mesh_roofline


def read(run):
    nbytes = sum(mesh_roofline.compact_bytes(a["rows"], a["cap"])
                 for a in mesh_roofline.scans(run))
    return mesh_roofline.share(run, mesh_roofline.COMPACT_KERNEL, nbytes)
