"""The ragged decode-attention kernel (``ops.pallas.decode_attention``)
against the dense mathematics of ``serving.hybrid._slot_attention``, in
interpret mode at tiny widths with blocks of 16 positions, and what the
engine counts of it.  Its compile for a described v5e is with the other
kernels' (``tests/test_flash_attention.py``: one file describes the
topology)."""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import metrics, serving, tracing
from mxnet_tpu.gluon.model_zoo import phi4flash as pf
from mxnet_tpu.ops.pallas import decode_attention as da

BLOCK = 16
PAIRS, G, D = 3, 2, 8           # K/V pairs, query pairs a K/V pair, head
KV, WIDTH = PAIRS * 2 * D, PAIRS * G * 2 * D


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(da, "ROW_BLOCK", BLOCK)


def positions(L):
    """A slot at 0, the last column of a block, the first of the next,
    the bucket's last, a free slot (it rides at 0) and one inside."""
    return np.asarray([0, BLOCK - 1, BLOCK, L - 1, 0, BLOCK + 5], np.int32)


def operands(L, dtype, seed=0):
    rng = np.random.RandomState(seed)
    S = len(positions(L))
    return (jnp.asarray(rng.randn(S, WIDTH), dtype),
            jnp.asarray(rng.randn(S, KV, L), dtype),
            jnp.asarray(rng.randn(S, KV, L), dtype))


def dense(q, ck, cv, pos):
    """``_slot_attention`` up to its second product.  The operands are
    widened first (the CPU has no bfloat16 x bfloat16 -> float32 dot at
    these shapes); their products are exact in float32 either way, and
    the probabilities are rounded to the rows' dtype as there."""
    S, L = q.shape[0], ck.shape[2]
    f32 = lambda a: a.astype(jnp.float32)           # noqa: E731
    scores = jnp.einsum("sngjd,snjdl->sngjl",
                        f32(q).reshape(S, PAIRS, G, 2, D),
                        f32(ck).reshape(S, PAIRS, 2, D, L),
                        precision="highest") / math.sqrt(D)
    visible = jnp.arange(L)[None, :] <= pos[:, None]
    probs = jax.nn.softmax(jnp.where(visible[:, None, None, None, :],
                                     scores, -jnp.inf), axis=-1)
    return jnp.einsum("sngjl,snel->sngje", f32(probs.astype(cv.dtype)),
                      f32(cv).reshape(S, PAIRS, 2 * D, L),
                      precision="highest")


@pytest.mark.parametrize("L", [2 * BLOCK, 4 * BLOCK])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 1e-2)])
def test_kernel_matches_dense_attention_at_per_slot_positions(L, dtype,
                                                              tol):
    q, ck, cv = operands(L, dtype)
    pos = jnp.asarray(positions(L))
    got = da.paired_decode_attention(q, ck, cv, pos, D)
    want = dense(q, ck, cv, pos)
    assert got.shape == want.shape == (len(pos), PAIRS, G, 2, 2 * D)
    assert got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) <= tol * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("L", [2 * BLOCK, 4 * BLOCK])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_what_lies_past_a_slots_position_never_reaches_the_result(L, dtype):
    """Every column past ``pos[s]`` poisoned with NaN: the dead blocks
    are not read and the last live block is masked, in K and in V."""
    q, ck, cv = operands(L, dtype, seed=1)
    pos = jnp.asarray(positions(L))
    dead = jnp.arange(L)[None, None, :] > pos[:, None, None]
    clean = da.paired_decode_attention(q, ck, cv, pos, D)
    poisoned = da.paired_decode_attention(
        q, jnp.where(dead, jnp.nan, ck), jnp.where(dead, jnp.nan, cv),
        pos, D)
    assert bool(jnp.isfinite(poisoned).all())
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(clean))


def test_a_bucket_shorter_than_a_block_is_one_block():
    L = BLOCK // 2
    q, ck, cv = operands(2 * BLOCK, jnp.float32, seed=2)
    ck, cv = ck[:, :, :L], cv[:, :, :L]
    pos = jnp.asarray([0, L - 1, 3, 0, 1, L - 2], jnp.int32)
    got = da.paired_decode_attention(q, ck, cv, pos, D)
    want = dense(q, ck, cv, pos)
    assert float(jnp.abs(got - want).max()) <= 2e-6 * float(
        jnp.abs(want).max())
    assert da.row_block(L) == L
    assert da.blocks_read(np.asarray(pos), L) == (6, 6)


@pytest.mark.parametrize("L", [2 * BLOCK, 4 * BLOCK])
def test_blocks_read_counts_each_slots_live_blocks(L):
    pos = positions(L)
    read, every = da.blocks_read(pos, L)
    assert read == 1 + 1 + 2 + L // BLOCK + 1 + 2
    assert every == len(pos) * L // BLOCK and read <= every


def test_rows_that_are_not_whole_blocks_are_refused():
    q, ck, cv = operands(2 * BLOCK, jnp.float32)
    with pytest.raises(ValueError, match="whole blocks"):
        da.paired_decode_attention(q, ck[:, :, :BLOCK + 8],
                                   cv[:, :, :BLOCK + 8],
                                   jnp.zeros((6,), jnp.int32), D)


# ---------------------------------------------------------------------------
# what the engine counts of it
# ---------------------------------------------------------------------------

def _row_counters():
    return (metrics.value("mxnet_gen_row_blocks_read_total"),
            metrics.value("mxnet_gen_row_blocks_total"))


def _dispatches(since):
    return [s for s in tracing.spans()
            if s["name"] == "model.step.dispatch" and s["t_begin"] >= since]


def test_the_engine_counts_the_blocks_each_launch_reads():
    """A scripted run of a tiny hybrid engine across two buckets: every
    launch's span carries ``row_blocks`` of ``row_blocks_all``, they are
    what the launch's own positions give, and the two counters move by
    their sums."""
    mx.random.seed(11)
    net = pf.get_phi4flash("tiny")
    net.initialize()
    model = serving.DecodeModel.from_block(net)
    engine = serving.GenerationEngine(model, max_slots=4,
                                      kv_buckets=(64, 128), prefix_slots=0,
                                      max_tokens=64)
    rng = np.random.default_rng(0)
    launches = []
    dispatch = model.dispatch

    def spy(cache, tokens, pos, sampling=None):
        launches.append((np.array(pos), cache.bucket))
        return dispatch(cache, tokens, pos, sampling)

    model.dispatch = spy
    read0, all0 = _row_counters()
    t0 = time.perf_counter()
    streams = [engine.submit(rng.integers(0, 503, n, dtype=np.int32),
                             max_new_tokens=new)
               for n, new in ((5, 40), (30, 50), (50, 30))]
    while engine.run_iteration():
        pass
    assert [len(s.result()) for s in streams] == [40, 50, 30]
    spans = _dispatches(t0)
    assert len(spans) == len(launches) > 40
    want = [(int((pos // BLOCK + 1).sum()), 4 * bucket // BLOCK)
            for pos, bucket in launches]
    assert [(s["attrs"]["row_blocks"], s["attrs"]["row_blocks_all"])
            for s in spans] == want
    assert {bucket for _, bucket in launches} == {64, 128}
    read, every = (b - a for a, b in zip((read0, all0), _row_counters()))
    assert read == sum(r for r, _ in want)
    assert every == sum(a for _, a in want)
    assert 0 < read < every


def test_a_dense_family_counts_no_row_blocks():
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    gpt = GPTModel(vocab_size=503, num_layers=2, units=64, hidden_size=128,
                   num_heads=4, max_length=128, dropout=0.0)
    gpt.initialize()
    gpt(mx.np.zeros((1, 4), dtype="int32"))
    model = serving.DecodeModel.from_block(gpt)
    assert model.row_blocks(np.zeros((2,), np.int32), 64) is None
    engine = serving.GenerationEngine(model, max_slots=2,
                                      kv_buckets=(64, 128), prefix_slots=0)
    before = _row_counters()
    t0 = time.perf_counter()
    stream = engine.submit(np.arange(9, dtype=np.int32), max_new_tokens=5)
    while engine.run_iteration():
        pass
    assert len(stream.result()) == 5
    spans = _dispatches(t0)
    assert spans and all("row_blocks" not in s["attrs"]
                         and "row_blocks_all" not in s["attrs"]
                         and "ahead" in s["attrs"] for s in spans)
    assert _row_counters() == before
