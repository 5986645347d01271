"""Bytes one decode step of the looped (Ouro) family must touch, from
shapes and the live slots' positions alone (as ``flops.py``: required
work only, no padding, nothing read twice that could be read once).

A token makes ``loop_steps`` passes over the SAME ``layers`` layers, and
pass ``t + 1`` of the first layer needs pass ``t`` of the last, so
nothing of a layer stays on the chip from one pass to the next (a layer
is 103 MB in bfloat16): a step reads every layer's weights
``loop_steps`` times.  It reads the untied head once (the embedding's
few rows are left out).  For each live slot at position ``pos`` (the
tokens it holds) it reads ``pos`` K and V rows in EVERY entry of the
cache, one entry a (loop step, layer): to the position, never the bucket
or the 512-position block.

The new column each entry gains a step and the activations are three
orders of magnitude below this and are left out.  Every term is at most
what the program reads, so the shares of the peak built on these bytes
cannot pass 100.

``arch`` is the configuration's group: ``layers``, ``loop_steps``,
``width``, ``heads``, ``head_dim``, ``ffn``, ``vocab``.
"""
import numpy as np


def entries(arch):
    """K/V cache entries a position: one a (loop step, layer)."""
    return arch["loop_steps"] * arch["layers"]


def layer_bytes(arch, itemsize):
    """One layer: q, k, v and output projections, gate, up and down, the
    four norm gains."""
    w, a = arch["width"], arch["heads"] * arch["head_dim"]
    return (4 * w * a + 3 * w * arch["ffn"] + 4 * w) * itemsize


def step_weight_bytes(arch, itemsize):
    """The weights a step must read: the layers once a loop step, the
    final norm with them, the head once."""
    w = arch["width"]
    return (arch["loop_steps"] * (arch["layers"] * layer_bytes(arch, itemsize)
                                  + w * itemsize)
            + arch["vocab"] * w * itemsize)


def row_bytes(arch, itemsize):
    """One position's K and V row in ONE entry."""
    return 2 * arch["heads"] * arch["head_dim"] * itemsize


def live_row_equivalents(samples, arch):
    """Mean over ``samples`` (each the live slots' positions at one
    moment) of the rows a step must read over all entries, in units of
    ``row_bytes``: what ``metrics/decode_hbm_pct.py`` takes beside the
    weights; None without samples."""
    if not samples:
        return None
    return float(np.mean([np.asarray(pos, np.float64).sum()
                          for pos in samples]) * entries(arch))


def attn_bytes(samples, arch, itemsize):
    """The live K and V a step's attention must read: what
    ``metrics/decode_attn_roofline_pct.py`` holds the ragged kernel's
    calls of a step to; None without samples."""
    rows = live_row_equivalents(samples, arch)
    return None if rows is None else rows * row_bytes(arch, itemsize)
