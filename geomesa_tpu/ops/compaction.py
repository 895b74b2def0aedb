"""Stream compaction: a bool row mask -> the first ``size`` true indices.

``jnp.nonzero(mask, size=k)`` lowers to ``cumsum(bincount(cumsum(mask),
length=k))``: a scatter-add of every row into k bins. Neighbouring rows
almost always share a bin, so on the TPU the scatter serialises on its
collisions (~300 ms over a 33.5M-row mirror for k = 8192 on one v5e,
against ~0.2 ms to stream the mask). ``compact_indices`` returns the same
array with no scatter over the rows:

1. per-block popcounts of the mask (one streaming reduce over the rows)
   and their inclusive cumsum over blocks;
2. a binary search of each output slot's rank in the block cumsum, which
   picks the block holding that slot's true row;
3. inside the picked block (one gathered row of ``_BLOCK`` flags per
   slot), the row's position is the number of flags whose inclusive
   prefix count stays at or below the slot's rank in the block.

All the work past step 1 is proportional to ``size`` and the block count,
not to the rows, and no row-length temporary beyond the mask is built.
On one v5e at 33.5M rows the density pass takes 41 ms with it against
344 ms with ``jnp.nonzero``, and the two RLE compactions of a mask take
2.6 ms against 486 ms at 4096 runs. Blocks of 128 to 512 flags time
alike there; a flat int32 cumsum with one search over the rows costs
~11 ms more (the row-length scan and its 4-byte-a-row temporary).
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

_BLOCK = 256  # flags per block: one gathered row per output slot


def compact_indices(mask, size: int, fill_value) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(idx, count): ``idx`` is exactly
    ``jnp.nonzero(mask, size=size, fill_value=fill_value)[0]`` as int32
    (ascending true indices, ``fill_value`` past the true count) and
    ``count`` the int32 number of true entries of the 1-D ``mask``."""
    n = mask.shape[0]
    if n == 0:
        return jnp.full((size,), fill_value, jnp.int32), jnp.int32(0)
    slot = jnp.arange(size, dtype=jnp.int32)
    nb = -(-n // _BLOCK)
    if nb * _BLOCK != n:
        mask = jnp.pad(mask, (0, nb * _BLOCK - n))
    blocks = mask.reshape(nb, _BLOCK)
    counts = jnp.sum(blocks, axis=1, dtype=jnp.int32)
    ends = jnp.cumsum(counts)  # inclusive true count through each block
    count = ends[-1]
    # the block holding slot j's true row: the first whose inclusive count
    # passes j (clamped past the count, where the slot takes fill_value)
    b = jnp.minimum(
        jnp.searchsorted(ends, slot, side="right", method="scan"), nb - 1
    ).astype(jnp.int32)
    rank = slot - (ends[b] - counts[b])
    prefix = jnp.cumsum(blocks[b].astype(jnp.int32), axis=1)
    pos = jnp.sum((prefix <= rank[:, None]).astype(jnp.int32), axis=1)
    return jnp.where(slot < count, b * _BLOCK + pos, jnp.int32(fill_value)), count
