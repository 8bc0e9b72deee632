"""The one import site of ``shard_map`` (analysis rule TD004): everything
else in ``tpu_dist`` imports it from here. The supported JAX is stated once,
in the README's "Running" section."""

from jax import shard_map

__all__ = ["shard_map"]
