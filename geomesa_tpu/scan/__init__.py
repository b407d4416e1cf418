"""L6 device scan kernels (SURVEY.md 2.2 iterators/coprocessors).

The reference pushes filtering and aggregation into the database's scan
machinery (Accumulo iterators, HBase coprocessors); here the equivalents
are fused, jitted JAX kernels over columnar device arrays.  A "scan" is
one XLA program: predicate masks + optional aggregation, executed on the
shard holding the data, with ICI collectives as the reduce.
"""

from .zscan import (DeviceScanData, ScanQuery, boundary_candidates,
                    build_scan_data, exact_patch, make_query, scan_mask,
                    split_two_float)

__all__ = ["DeviceScanData", "ScanQuery", "boundary_candidates",
           "build_scan_data", "exact_patch", "make_query", "scan_mask",
           "split_two_float"]
