from repro.sharding.rules import (
    param_specs,
    opt_state_specs,
    batch_spec,
    cache_specs,
    fleet_specs,
    host_resident_bytes,
    named,
    data_axes_of,
)

__all__ = ["param_specs", "opt_state_specs", "batch_spec",
           "cache_specs", "fleet_specs", "host_resident_bytes", "named",
           "data_axes_of"]
