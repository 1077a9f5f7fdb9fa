"""OMS search over a device mesh: the library sharded across the ``model``
axis, per-shard winners merged by all-gather (``collectives``)."""
