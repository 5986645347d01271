"""Bytes one decode step of the delta-rule hybrid (Olmo Hybrid) family
must touch, from shapes and the live slots' positions alone (as
``flops.py``: required work only, no padding, nothing read twice that
could be read once).

A step reads every layer's weights once, the final norm and the untied
head (the embedding's few rows are left out).  For each live slot at
position ``pos`` (the tokens it holds) it also

* reads and writes the slot's float32 matrix state (``lin_heads x
  key_dim x value_dim``) and the conv's ``conv_size - 1`` float32 inputs
  (over ``2 lin_heads key_dim + lin_heads value_dim`` channels) in each
  linear layer: the same at every position;
* reads ``pos`` K and V rows in each full layer, to the position.

The new column each full layer gains a step and the activations are
three orders of magnitude below this and are left out.  Every term is
at most what the program moves, so the shares of the peak built on
these bytes cannot pass 100.

``arch`` is the configuration's group: ``width``, ``ffn``, ``vocab``,
``heads``, ``head_dim``, ``lin_heads``, ``key_dim``, ``value_dim``,
``conv_size`` and the layer counts ``linear_layers``, ``full_layers``.
"""
import numpy as np


def matrix_state_bytes(arch):
    """One slot's matrix state in ONE linear layer."""
    return 4 * arch["lin_heads"] * arch["key_dim"] * arch["value_dim"]


def conv_channels(arch):
    h = arch["lin_heads"]
    return h * (2 * arch["key_dim"] + arch["value_dim"])


def state_bytes(arch):
    """One slot's state, matrix and conv inputs, in ONE linear layer."""
    return matrix_state_bytes(arch) \
        + 4 * (arch["conv_size"] - 1) * conv_channels(arch)


def layer_bytes(arch, itemsize):
    """(a linear layer's weights, a full layer's weights) in bytes."""
    w, ffn, h = arch["width"], arch["ffn"], arch["lin_heads"]
    value = h * arch["value_dim"]
    mlp = 3 * w * ffn + 2 * w
    linear = ((conv_channels(arch) + value + 2 * h) * w + value * w
              + conv_channels(arch) * arch["conv_size"]
              + arch["value_dim"] + mlp) * itemsize + 2 * 4 * h
    a = arch["heads"] * arch["head_dim"]
    full = (4 * w * a + 2 * a + mlp) * itemsize
    return linear, full


def step_weight_bytes(arch, itemsize):
    """The weights a step must read: every layer, the final norm, the
    head."""
    linear, full = layer_bytes(arch, itemsize)
    w = arch["width"]
    return (arch["linear_layers"] * linear + arch["full_layers"] * full
            + (w + arch["vocab"] * w) * itemsize)


def row_bytes(arch, itemsize):
    """One position's K and V row in one full layer."""
    return 2 * arch["heads"] * arch["head_dim"] * itemsize


def slot_bytes(pos, arch, itemsize):
    """What the live slots at positions ``pos`` (array) add to a step."""
    pos = np.asarray(pos, np.float64)
    return float(pos.size * 2 * arch["linear_layers"] * state_bytes(arch)
                 + pos.sum() * arch["full_layers"]
                 * row_bytes(arch, itemsize))


def live_row_equivalents(samples, arch, itemsize):
    """Mean over ``samples`` (each the live slots' positions at one
    moment) of ``slot_bytes``, in units of ``row_bytes``: with the
    weights, what ``metrics/decode_hbm_pct.py`` takes as the bytes a
    step must touch; None without samples."""
    if not samples:
        return None
    return float(np.mean([slot_bytes(pos, arch, itemsize)
                          for pos in samples]) / row_bytes(arch, itemsize))


def attn_bytes(samples, arch, itemsize):
    """The live K and V a step's full layers must read (rows ``0 ..
    pos`` of every live slot in every full layer): what
    ``metrics/decode_attn_roofline_pct.py`` holds the ragged kernel's
    calls of a step to; None without samples."""
    if not samples:
        return None
    return float(np.mean([np.asarray(pos, np.float64).sum()
                          for pos in samples])) \
        * arch["full_layers"] * row_bytes(arch, itemsize)


def delta_state_bytes(samples, arch):
    """The float32 matrix state a step's delta kernel must read and
    write: every live slot's, in every linear layer (the conv's inputs
    are not the kernel's); None without samples.  What
    ``metrics/delta_step_roofline_pct.py`` holds the kernel's calls of a
    step to."""
    if not samples:
        return None
    return float(np.mean([len(pos) for pos in samples])) * 2 \
        * arch["linear_layers"] * matrix_state_bytes(arch)
