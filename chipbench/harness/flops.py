"""Operations and bytes the algorithms need, from shapes alone, and the
table of peaks (peaks.json, keyed by jax's ``device_kind``).

Only required work counts: no recomputation, no padding.  One
multiply-add is 2 FLOPs.
"""
import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind):
    """The published peaks of ``device_kind``; a device that is not in
    the table is an error, not a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS} (it has {sorted(table)})")
    return table[device_kind]


def block_params(width, ffn):
    """Matmul weights of one transformer block: qkv (3 w^2), attention
    output (w^2), two FFN matrices (2 w ffn)."""
    return 4 * width * width + 2 * width * ffn


def attention_flops_per_token(layers, seq, width, causal):
    """Forward + backward attention FLOPs a token: scores and
    probabilities-times-values are 2 matmuls forward (4 T w FLOPs a
    token a layer) and 4 backward, so 12 L T w; a causal mask needs
    half of each."""
    full = 12 * layers * seq * width
    return full / 2 if causal else full


def train_flops_per_token(layers, width, ffn, seq, causal, head_flops):
    """Forward + backward FLOPs per input token: 6 N over the blocks'
    matmul weights (2 forward, 4 backward) plus attention, plus
    ``head_flops`` (the output head's own 6 x weights x positions,
    already divided by the tokens of a sequence)."""
    return (6 * layers * block_params(width, ffn)
            + attention_flops_per_token(layers, seq, width, causal)
            + head_flops)


def mlm_head_flops_per_token(width, vocab, masked, seq):
    """The MLM head runs on the masked positions only: a w x w transform
    and the w x vocab decoder, 6 FLOPs a weight a position, spread over
    the sequence's tokens."""
    return 6 * (width * width + width * vocab) * masked / seq


def lm_head_flops_per_token(width, vocab):
    return 6 * width * vocab


def flash_flops_and_bytes(batch, seq, width, layers, causal, itemsize):
    """What the attention kernels of one training step need, all layers:
    12 B T^2 w FLOPs (see attention_flops_per_token), and one pass over
    q, k, v, o forward (4 tensors) and q, k, v, o, do, dq, dk, dv
    backward (8 tensors) of B T w elements each."""
    flops = batch * seq * attention_flops_per_token(layers, seq, width,
                                                    causal)
    nbytes = 12 * batch * seq * width * itemsize * layers
    return flops, nbytes


def decode_step_bytes(param_bytes, live_kv_rows, layers, width, itemsize):
    """Bytes one decode step must read: every weight once, and the K and
    V rows of the tokens the live sequences hold (2 L w elements a
    row)."""
    return param_bytes + live_kv_rows * 2 * layers * width * itemsize
