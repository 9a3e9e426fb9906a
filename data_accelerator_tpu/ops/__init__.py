"""XLA/Pallas kernels over columnar batches.

These take the role Spark's execution engine plays for the reference
(shuffle/aggregate/join inside ``spark.sql`` — CommonProcessorFactory.
scala:249-293): static-shape, mask-aware primitives that XLA fuses and
tiles onto the VPU/MXU.
"""

from .groupby import (
    distinct_mask,
    group_ids,
    segment_aggregate,
    segment_starts,
    sort_groups,
)
from .join import inner_join_indices
from .compact import compact_indices

__all__ = [
    "group_ids",
    "sort_groups",
    "segment_starts",
    "segment_aggregate",
    "distinct_mask",
    "inner_join_indices",
    "compact_indices",
]
