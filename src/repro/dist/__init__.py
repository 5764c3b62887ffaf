"""Distributed subsystem: sharding vocabulary + GRASP-aware collectives.

``repro.dist.sharding`` is the PartitionSpec/NamedSharding vocabulary used
by the launch layer (steps/dryrun/train/serve); ``repro.dist.collectives``
is the GRASP distributed exchange — hot-prefix replication with a bounded
cold halo (paper Table I lifted to the partition tier).
"""
