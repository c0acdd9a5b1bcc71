"""Host-visible device synchronization: a fence that holds on every
backend.

`hard_sync` forces a real round trip by materializing one element of
one array leaf on the host.  `jax.device_get` cannot return before the
producing computation, and everything queued ahead of it on the device
stream, has executed -- whatever a backend's `block_until_ready` waits
for.  Every timing loop and every calibration (ChunkedEks, the bench
windows, worker warmup) syncs through it, so none of them can measure
enqueue speed by accident, and a runtime fault in a dispatch surfaces
at the fence instead of on some later batch.  Cost: one small
device-to-host read per call -- sync a whole depth-window of
dispatches, never each one.
"""

from __future__ import annotations

import numpy as np


def hard_sync(tree) -> None:
    """Block until every array in `tree` (any pytree) has actually been
    computed, by fetching one element of ONE leaf to the host.

    One fetch suffices: the device stream executes in order, so a
    gather enqueued after the producing dispatches can only yield its
    value once everything ahead of it has run -- including every other
    leaf of the same pytree.  The remaining leaves get a plain
    block_until_ready."""
    import jax

    fetched = False
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and not fetched and leaf.size:
            if leaf.ndim == 0:
                np.asarray(jax.device_get(leaf))
            else:
                # one-element slice: the gather is a dispatch that
                # depends on `leaf`, so fetching it fences everything
                # queued before it without transferring the buffer
                np.asarray(jax.device_get(leaf.ravel()[0]))
            fetched = True
        elif isinstance(leaf, jax.Array):
            jax.block_until_ready(leaf)
        else:
            np.asarray(leaf)
