"""Parallelism substrate: logical shardings, mesh helpers, collectives."""
from .sharding import (batch_axes, constrain, constrain_batch, current_mesh,  # noqa: F401
                       filter_spec, named_sharding, sanitize_spec,
                       shard_map_unchecked, tree_shardings,
                       tree_shardings_shaped)
