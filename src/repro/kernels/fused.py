"""Recognition of fused-epilogue Pallas kernels inside traced jaxprs.

The flash_attention / rwkv6 kernels optionally take a (4,) int32 runtime
format row as an SMEM scalar-prefetch operand and apply the dynamic
quantize as an in-kernel epilogue on their output stores (see
``quantize_em.ref.quantize_epilogue``). When the interpreter's table/policy
transform meets such a ``pallas_call`` equation it can *route* the site's
format row into the existing epilogue — substituting the prefetch operand —
instead of appending a separate quantize kernel after it: a found policy
then executes as one fused kernel per site.

Routing is sound because the epilogue is bit-identical to
``ops.quantize_dynamic`` applied to the stored value, and model code wires
the hook with ``IDENTITY_ROW`` (an exact passthrough), so replacing the row
is exactly "quantize this site's output" with zero extra kernels. The
contract is that the call-site row is the *default* for an untruncated
site; the policy row replaces it.

Kept free of any ``repro.core`` import so both the interpreter and the
kernel modules can use it while ``repro.core`` is still initializing.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

# pallas_call ``name`` -> output indices covered by the in-kernel epilogue
# (other outputs — e.g. rwkv6's recurrence state sT — are ordinary sites
# and keep the separate quantize pass)
FUSED_KERNELS = {
    "flash_attention": (0,),
    "wkv6": (0,),
}

_ROW_SHAPE = (4,)


def fused_outputs(eqn) -> Optional[Tuple[int, ...]]:
    """Output indices covered by a fused quantize epilogue, or ``None``.

    Recognition is structural: a ``pallas_call`` whose grid mapping
    prefetches exactly one scalar operand, whose first operand is a (4,)
    int32 row, and whose kernel is one of the known epilogue-bearing
    kernels (by the ``name`` the kernel module gives its ``pallas_call``)."""
    if eqn.primitive.name != "pallas_call":
        return None
    gm = eqn.params.get("grid_mapping")
    if gm is None or getattr(gm, "num_index_operands", None) != 1:
        return None
    aval = eqn.invars[0].aval
    if (getattr(aval, "shape", None) != _ROW_SHAPE
            or getattr(aval, "dtype", None) != np.dtype(np.int32)):
        return None
    return FUSED_KERNELS.get(eqn.params.get("name"))
