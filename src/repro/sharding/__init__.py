from repro.sharding.specs import (
    batch_spec,
    cache_specs,
    data_axes,
    param_specs,
    spec_for_array,
)

__all__ = [
    "param_specs",
    "batch_spec",
    "cache_specs",
    "data_axes",
    "spec_for_array",
]
