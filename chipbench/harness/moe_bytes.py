"""Bytes one decode step of the Command A+ (``cohere2_moe``) family must
touch, from shapes, the live slots' positions and the step's expert load
alone (as ``flops.py``: required work only, no padding, nothing read
twice that could be read once).

A step reads every weight OUTSIDE the routed experts once: each layer's
attention projections, its shared experts, its router and its norm, the
final norm and the rows of the tied embedding that are held (the output
head reads all of them).  Of the routed experts it reads the three
matrices of a HELD expert only in a step that routed at least one token
to it (``experts_hit``, which the program hands back with the tokens);
an expert no token chose need not be touched, and the batched product
that reads it anyway is not credited for it.  For each live slot at
position ``pos`` (the tokens it holds) it also reads ``min(pos,
window)`` K and V rows in each window layer and ``pos`` in each full
layer: to the position, never the bucket or the 512-position block.

The new column each cache gains a step and the activations are three
orders of magnitude below this and are left out.  Every term is at most
what the program reads, so the share of the peak built on these bytes
cannot pass 100.

``arch`` is the configuration's group: ``layers``, ``width``, ``heads``,
``kv_heads``, ``head_dim``, ``expert_width``, ``experts``,
``experts_held``, ``shared_experts``, ``vocab``, ``window``,
``window_layers``, ``full_layers``.

The decode step's expert product is XLA's (a batched ``dot_general``):
its share is ``decode_hbm_pct``.  Prefill's is a Pallas kernel (the
``megablox`` grouped matmul that ships inside jax), and
``gmm_flops_and_bytes`` below is what ``moe_gmm_roofline_pct`` holds it
to.
"""
import numpy as np


def expert_bytes(arch, itemsize):
    """One routed (or shared) expert: gate, up and down."""
    return 3 * arch["width"] * arch["expert_width"] * itemsize


def fixed_bytes(arch, itemsize):
    """Every weight outside the routed experts."""
    q, kv = (arch[n] * arch["head_dim"] for n in ("heads", "kv_heads"))
    layer = (arch["width"] * (2 * q + 2 * kv + arch["experts"] + 1)
             * itemsize + arch["shared_experts"] * expert_bytes(arch,
                                                                itemsize))
    return (arch["layers"] * layer
            + (arch["vocab"] + 1) * arch["width"] * itemsize)


def row_bytes(arch, itemsize):
    """One position's K and V row in one attention layer."""
    return 2 * arch["kv_heads"] * arch["head_dim"] * itemsize


def slot_rows(pos, arch):
    """K and V rows, over all layers, that the live slots at positions
    ``pos`` (array) make a step read."""
    pos = np.asarray(pos, np.float64)
    return float(np.minimum(pos, arch["window"]).sum()
                 * arch["window_layers"] + pos.sum() * arch["full_layers"])


def live_row_equivalents(samples, arch):
    """Mean over ``samples`` (each the live slots' positions at one
    moment) of ``slot_rows``: what ``metrics/decode_hbm_pct.py`` takes,
    times ``row_bytes``, as the cache bytes a step must read; None
    without samples."""
    if not samples:
        return None
    return float(np.mean([slot_rows(pos, arch) for pos in samples]))


def step_weight_bytes(experts_hit, arch, itemsize):
    """The weights a step that hit ``experts_hit`` held experts (summed
    over layers; a mean over steps may be fractional) must read."""
    return fixed_bytes(arch, itemsize) \
        + experts_hit * expert_bytes(arch, itemsize)


def gmm_flops_and_bytes(assignments, experts_hit, arch, itemsize):
    """What the grouped product of ONE prefill needs, all layers
    (``parallel.moe.grouped_experts``: two ``megablox`` grouped matmuls
    a layer): ``assignments`` (token, choice) pairs routed to held
    experts and ``experts_hit`` held experts with at least one, both
    summed over layers, as the ``model.prefill`` span says them.  Every
    routed row goes through gate, up and down once (6 w f FLOPs a row);
    every hit expert's three matrices are read once; a row is read in
    (w) and written out (2 f float32), read again (f) and written out
    (w float32)."""
    w, f = arch["width"], arch["expert_width"]
    flops = 6.0 * assignments * w * f
    nbytes = experts_hit * expert_bytes(arch, itemsize) \
        + assignments * ((w + f) * itemsize + (2 * f + w) * 4)
    return flops, nbytes
