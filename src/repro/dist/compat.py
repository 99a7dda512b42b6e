"""The SPMD substrate's ``shard_map``: ``jax.shard_map`` of the installed
jax (0.9), whose replication-check keyword is ``check_vma``."""

from __future__ import annotations

from jax import shard_map

__all__ = ["shard_map"]
