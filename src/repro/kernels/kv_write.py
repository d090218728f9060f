"""In-place write of one decode tick's K/V rows into a donated TPU cache.

On TPU the cache of shape (..., S, hd) keeps the sequence minor in memory
(a head size such as 80 is not a multiple of the 128 lanes, the sequence
is), so one token's row is one lane of hd/16 tiles per head. XLA writes it
with one ``dynamic_update_slice`` a slot, tile by tile; a scatter has it
copy the whole cache into another layout and back. This kernel takes the
cache as its (..., hd, S) transpose, which XLA hands over as a bitcast of
the same buffer, and rewrites, per (layer, slot), the 128-lane block that
holds the slot: every head and the whole head size in one block, aliased
in place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def kv_write(k, v, k_rows, v_rows, slot, *, interpret: bool = False):
    """k, v: (L, B, H, hd, S) caches, sequence last, S a multiple of 128;
    k_rows, v_rows: (L, B, H, hd); slot: (B,) int32. Returns the caches
    with row (l, b) at sequence index ``slot[b]``; a slot at or past S
    writes nothing. The caches are aliased to the outputs: donate them."""
    L, B, H, hd, S = k.shape
    n_blocks = S // LANES

    def kv_write_kernel(slot_ref, kr_ref, vr_ref, kc_ref, vc_ref, ko_ref,
                        vo_ref):
        s = slot_ref[pl.program_id(1)]
        base = jnp.minimum(s // LANES, n_blocks - 1) * LANES
        hit = lax.broadcasted_iota(jnp.int32, (hd, LANES), 1) == s - base
        for h in range(H):
            # the head's row, head size on sublanes, one lane: broadcast
            # along the block's lanes and kept where the slot is
            ko_ref[0, 0, h] = jnp.where(hit, kr_ref[0, 0, :, h:h + 1],
                                        kc_ref[0, 0, h])
            vo_ref[0, 0, h] = jnp.where(hit, vr_ref[0, 0, :, h:h + 1],
                                        vc_ref[0, 0, h])

    def cache_block(l, b, slot_ref):
        return l, b, 0, 0, jnp.minimum(slot_ref[b] // LANES, n_blocks - 1)

    def row_block(l, b, slot_ref):
        return l, b, 0, 0

    cache = pl.BlockSpec((1, 1, H, hd, LANES), cache_block)
    rows = pl.BlockSpec((1, 1, hd, H), row_block)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(L, B),
        in_specs=[rows, rows, cache, cache], out_specs=[cache, cache])
    return pl.pallas_call(
        kv_write_kernel, grid_spec=spec,
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        input_output_aliases={3: 0, 4: 1}, interpret=interpret,
        name="kv_write")(
            slot, jnp.swapaxes(k_rows, -1, -2), jnp.swapaxes(v_rows, -1, -2),
            k, v)
