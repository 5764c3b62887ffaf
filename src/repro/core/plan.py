"""GraspPlan — the compile-time residency plan (TPU adaptation of the ABRs).

On a TPU there is no transparent LLC; fast-memory residency is a *software*
decision. ``GraspPlan`` carries exactly the information the paper's ABRs +
classification logic provide, resolved at plan time:

  * ``hot_size``       number of leading Property-Array elements (after
                       skew-aware reordering) that fit the fast-memory
                       budget — the High Reuse Region.
  * ``moderate_size``  the next budget's worth — the Moderate Reuse Region.
  * element geometry   so byte bounds can be recovered for the LLC
                       simulator / trace generator.

The same plan object drives three tiers:
  1. the Pallas ``hot_gather``/``embedding_bag`` kernels (hot prefix pinned
     in VMEM, cold streamed from HBM),
  2. the distributed property exchange (hot prefix replicated across chips,
     cold partitioned — ``dist/collectives.py``),
  3. the LLC simulator's hint stream (faithful paper reproduction).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.core.regions import GraspRegions, make_regions

# Scoped VMEM one Pallas kernel may use on TPU v5e at the compiler's default
# limit. Mosaic states it when a kernel asks for more: "Scoped allocation
# with size 17.00M and limit 16.00M exceeded scoped vmem limit".
KERNEL_VMEM_BYTES = 16 << 20
SUBLANES = 8


def entries_for_budget(
    budget_bytes: int,
    elem_bytes: int,
    align: int = 1,
    max_entries: Optional[int] = None,
) -> int:
    """How many ``elem_bytes``-sized rows fit a fast-memory byte budget.

    The one bytes->entries conversion shared by every residency tier: the
    kernel plan (``make_plan``), the distributed hot-replica sizing
    (``dist.collectives.partition_spec_for``) and the serving cache
    (``serve.cache``). ``align`` rounds down to a multiple (tile-aligned
    hot blocks); ``max_entries`` clamps to the table length.
    """
    n = max(int(budget_bytes), 0) // max(int(elem_bytes), 1)
    if max_entries is not None:
        n = min(n, int(max_entries))
    if align > 1:
        n -= n % align
    return int(n)


def kernel_hot_rows(row_bytes: int, tile_rows: int,
                    max_rows: Optional[int] = None) -> int:
    """Rows of a VMEM-pinned hot block that fit one kernel's scoped VMEM.

    ``row_bytes`` is the lane-padded row size. The kernel's other VMEM user
    is its double-buffered ``(tile_rows, row)`` output tile. The result is
    a multiple of the sublane tile, then clamped to ``max_rows``. Every
    VMEM-pinned tier asks here: the serving cache and the kernel wrappers.
    """
    budget = KERNEL_VMEM_BYTES - 2 * tile_rows * row_bytes
    n = entries_for_budget(budget, row_bytes, align=SUBLANES)
    return n if max_rows is None else min(n, int(max_rows))


@dataclasses.dataclass(frozen=True)
class GraspPlan:
    num_elems: int          # Property Array length (vertices / table rows)
    elem_bytes: int         # bytes per element (after array merging)
    hot_size: int           # elements in the High Reuse Region
    moderate_size: int      # elements in the Moderate Reuse Region
    budget_bytes: int       # fast-memory budget backing hot_size
    num_arrays: int = 1     # Property Arrays sharing the budget

    @property
    def enabled(self) -> bool:
        return self.hot_size > 0

    @property
    def cold_size(self) -> int:
        return self.num_elems - self.hot_size

    def regions(self) -> GraspRegions:
        """Byte-granular region view for the LLC simulator.

        The High Reuse Region covers exactly ``hot_size`` elements, which
        already embodies the paper's LLC_size / num_arrays division.
        """
        return make_regions(
            [(0, self.num_elems * self.elem_bytes)],
            llc_bytes=max(self.hot_size * self.elem_bytes, 1),
        )

    def classify_elem(self, idx: np.ndarray) -> np.ndarray:
        """0=hot, 1=moderate, 2=cold for element indices (range test)."""
        idx = np.asarray(idx)
        return np.where(
            idx < self.hot_size,
            0,
            np.where(idx < self.hot_size + self.moderate_size, 1, 2),
        ).astype(np.int8)


def make_plan(
    num_elems: int,
    elem_bytes: int,
    budget_bytes: int,
    num_arrays: int = 1,
    align: int = 1,
) -> GraspPlan:
    """Size the High/Moderate regions from a fast-memory budget.

    ``align`` rounds hot_size down to a multiple (kernels want tile-aligned
    hot blocks). On no-skew inputs the plan is identical — robustness comes
    from the *policies* staying flexible, not from disabling the plan
    (paper Sec. V-B).
    """
    per_array = budget_bytes // max(num_arrays, 1)
    hot = entries_for_budget(per_array, elem_bytes, align=align,
                             max_entries=num_elems)
    mod = min(per_array // elem_bytes, num_elems - hot)
    return GraspPlan(
        num_elems=int(num_elems),
        elem_bytes=int(elem_bytes),
        hot_size=int(hot),
        moderate_size=int(mod),
        budget_bytes=int(budget_bytes),
        num_arrays=int(num_arrays),
    )
