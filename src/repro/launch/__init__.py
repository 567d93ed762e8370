"""Launch layer: mesh construction, perf models, and the DTM server."""
from .mesh import (make_production_mesh, make_host_mesh, HardwareModel,
                   PEAKS, V5E, hardware_model, mesh_chips, data_axes)

__all__ = ["make_production_mesh", "make_host_mesh", "HardwareModel",
           "PEAKS", "V5E", "hardware_model", "mesh_chips", "data_axes"]

# NOTE: the multi-tenant DTM server lives in repro.launch.serve_tm and
# the async continuous-batching runtime in repro.launch.scheduler
# (imported lazily there — they pull in the full repro.api front-end;
# `api.serve(roster)` builds the whole stack in one call).
