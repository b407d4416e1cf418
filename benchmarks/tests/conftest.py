"""The benchmark's own tests run on the CPU: ``JAX_PLATFORMS=cpu`` is set
before JAX is imported, with four virtual devices so a cell of four chips
can be rehearsed, and the scan tiers' host cap is lowered (a program
property, ``geomesa.scan.host.rows``) so a table of a few hundred thousand
rows still reaches the gathered and dense device tiers."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()
os.environ["GEOMESA_SCAN_HOST_ROWS"] = "2000"
sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
