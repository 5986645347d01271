"""Bytes one decode step of the Phi-4-mini-flash family must touch, from
shapes and the live slots' positions alone (as ``flops.py``: required
work only, no padding, nothing read twice that could be read once).

A step reads every weight once (the tied embedding is the output head,
so all of it).  For each live slot at position ``pos`` (the tokens it
holds) it also

* reads and writes the slot's recurrent state and conv tail, float32,
  in each Mamba layer;
* reads ``min(pos, window)`` K and V rows in each window layer;
* reads ``pos`` K and V rows of the one full-attention layer once for
  that layer and once for each cross-attention layer that shares them.

The new row each cache gains a step and the activations are three
orders of magnitude below this and are left out.

``arch`` is the configuration's group: ``width``, ``kv_heads``,
``head_dim``, ``window``, ``d_inner``, ``d_state``, ``d_conv`` and the
layer counts ``mamba_layers``, ``window_layers``, ``full_layers``,
``cross_layers``.
"""
import numpy as np


def state_bytes(arch):
    """One slot's recurrent state and conv tail over all Mamba layers."""
    return 4 * arch["mamba_layers"] * arch["d_inner"] * (
        arch["d_state"] + arch["d_conv"] - 1)


def row_bytes(arch, itemsize):
    """One position's K and V row in one attention layer."""
    return 2 * arch["kv_heads"] * arch["head_dim"] * itemsize


def slot_bytes(pos, arch, itemsize):
    """What the live slots at positions ``pos`` (array) add to a step."""
    pos = np.asarray(pos, np.float64)
    row = row_bytes(arch, itemsize)
    readers = arch["full_layers"] + arch["cross_layers"]
    return float(pos.size * 2 * state_bytes(arch)
                 + np.minimum(pos, arch["window"]).sum()
                 * arch["window_layers"] * row
                 + pos.sum() * readers * row)


def live_row_equivalents(samples, arch, itemsize):
    """Mean over ``samples`` (each the live slots' positions at one
    moment) of ``slot_bytes``, in units of ``row_bytes``: with the
    weights, what ``metrics/decode_hbm_pct.py`` takes as the bytes a
    step must read; None without samples."""
    if not samples:
        return None
    return float(np.mean([slot_bytes(pos, arch, itemsize)
                          for pos in samples]) / row_bytes(arch, itemsize))
