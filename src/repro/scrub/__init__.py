"""Scrub patroller + online shard rebuild (docs/api.md, docs/perf.md).

Continuous low-priority verification of protected state between the
paper's scheduled full scrubs, plus reconstruction of a lost shard from
cross-shard parity while the foreground keeps running.  Enabled via
``RedundancyPolicy.patrol_bytes_per_tick``; see :mod:`repro.scrub.patrol`.
"""
from .patrol import (MAX_REPAIR_ATTEMPTS, ScrubPatroller,
                     ShardLossConflictError)
from .rebuild import (CrossShardParity, RebuildStatus, ShardRebuilder,
                      pack_mask_np)

__all__ = [
    "ScrubPatroller", "MAX_REPAIR_ATTEMPTS",
    "ShardRebuilder", "RebuildStatus", "CrossShardParity", "pack_mask_np",
    "ShardLossConflictError",
]
