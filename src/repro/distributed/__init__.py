from repro.distributed.api import (  # noqa: F401
    ambient_mesh, constrain, dp_axes, has_axis, mesh_axes, P,
)
