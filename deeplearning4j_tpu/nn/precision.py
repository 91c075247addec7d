"""Mixed-precision casting helpers shared by the MultiLayerNetwork and
ComputationGraph training paths (one protocol, two containers): fwd/bwd in
the compute dtype, loss head + regularization + carried state in the
parameter dtype."""
from __future__ import annotations

import jax


def tree_cast(tree, dtype):
    """Cast every array leaf."""
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def restore_dtypes(tree, ref_tree):
    """Cast each leaf back to its counterpart's dtype (carried state must
    keep its original precision across steps or the jit retraces)."""
    return jax.tree.map(lambda a, b: a.astype(b.dtype), tree, ref_tree)


def wire_asarray(a, dtype, as_ids=False):
    """Host→device transfer policy, shared by every fit/scan/output path:
    float features are converted to the model dtype host-side (free — same
    byte count for f32), while compact non-float dtypes (uint8 pixels, int
    ids) cross the host link AS-IS and are cast/normalized on-device inside
    the compiled step (`_prep_features`/`_prep_inputs`): the host link is
    the slow leg of a fed step, and uint8 is 4x fewer bytes than f32."""
    import jax.numpy as jnp
    import numpy as np

    # dtype probe without materializing: np.asarray on an already-on-device
    # jnp array would round-trip the whole batch through the host
    adtype = getattr(a, "dtype", None)
    if adtype is None:
        a = np.asarray(a)  # plain Python sequence
        adtype = a.dtype
    if as_ids:
        # destined for an integer-id consumer (embedding input or an
        # id-consuming normalizer): a FLOAT id array must not be cast to a
        # narrow model dtype (bf16 rounds ids above 256) — truncate to
        # int32 instead; integral dtypes ship compact as-is. An already-
        # on-device array casts on device (no host round trip).
        if jnp.issubdtype(adtype, np.floating):
            if isinstance(a, jnp.ndarray):
                return a.astype(jnp.int32)
            return jnp.asarray(np.asarray(a).astype(np.int32))
        return jnp.asarray(a)
    if jnp.issubdtype(adtype, np.floating):
        return jnp.asarray(a, dtype)
    return jnp.asarray(a)


def stack_wire(arrs, dtype, as_ids=False):
    """Stack a list of per-batch arrays for a scanned dispatch, with the
    same cast policy as `wire_asarray`. Already-device-resident batches
    (DeviceCacheDataSetIterator) stack ON DEVICE — np.stack would drag
    every batch back through the host link."""
    import jax.numpy as jnp
    import numpy as np

    if all(isinstance(a, jnp.ndarray) for a in arrs):
        x = jnp.stack(arrs)
        if as_ids:
            return x.astype(jnp.int32) if jnp.issubdtype(
                x.dtype, jnp.floating) else x
        if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != dtype:
            x = x.astype(dtype)
        return x
    return wire_asarray(np.stack([np.asarray(a) for a in arrs]), dtype,
                        as_ids)
