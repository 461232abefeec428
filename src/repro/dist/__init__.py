"""Distribution subsystem: meshes, sharding rules, HLO collective checks."""
from repro.dist import sharding  # noqa: F401
from repro.dist.config import (  # noqa: F401
    DistConfig, add_dist_args, parse_mesh, resolve_dist,
)
from repro.dist.sharding import (  # noqa: F401
    assert_no_cross_worker_collectives,
)
