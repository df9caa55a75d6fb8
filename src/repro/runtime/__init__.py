from repro.runtime.compat import make_mesh_compat
from repro.runtime.fault_tolerance import (
    ResilientLoop,
    StragglerMonitor,
    elastic_reshard,
)

__all__ = [
    "ResilientLoop",
    "StragglerMonitor",
    "elastic_reshard",
    "make_mesh_compat",
]
