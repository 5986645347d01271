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
from mxnet_tpu.ops.pallas import column_write as cw
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
# the appended walk (a looped family's pass in one call): the column
# written inside the read
# ---------------------------------------------------------------------------

def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


# (rows, the block the walk takes of them): every size append_block
# can return, and rows shorter than a tile column, which are one block
WALKS = [(1024, 128), (2048, 256), (4096, 512), (64, 64)]


@pytest.fixture
def real_blocks(monkeypatch):
    # the appended walk's block is an eighth of the rows between a tile
    # column and the grid's block: at the sizes of the cells
    monkeypatch.setattr(da, "ROW_BLOCK", 512)


@pytest.mark.parametrize("L,B", WALKS)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 1e-3)],
                         ids=["float32", "bfloat16"])
def test_append_and_attend_is_write_columns_then_ragged_attention(
        real_blocks, monkeypatch, L, B, dtype, tol):
    """The one call against the pair it replaced, on the same operands:
    the attention to float32 round-off where the pair walks the call's
    own blocks of ``B`` (the same arithmetic in the same order) and to
    the rows' round-off where it walks its grid's 512 (bfloat16
    probabilities are rounded under another running maximum); both
    stacks bit for bit what the column write leaves, so every other
    entry and every tile the walk did not write untouched.  Slots at 0,
    at the last column of a block, the first of the next, the rows'
    last, a free slot riding at 0, one inside."""
    assert da.append_block(L) == B
    E, G, R, C, entry = 3, 2, 1, 16, 1
    pos = np.asarray([0, B - 1, B % L, L - 1, 0, (B + 5) % L], np.int32)
    S = len(pos)
    rng = np.random.default_rng(L)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)  # noqa
    q, kn, vn = draw(S, G, R, C), draw(S, G * C), draw(S, G * C)
    K, V = draw(E, S, G * C, L), draw(E, S, G * C, L)
    at, e = jnp.asarray(pos), jnp.int32(entry)
    got, gk, gv = jax.jit(da.append_and_attend, static_argnums=6)(
        q, K, V, kn, vn, at, 0.25, e)
    wk, wv = cw.write_columns((K, V), (kn, vn), at, e)
    want = da.ragged_attention(q, wk, wv, at, 0.25, e)
    monkeypatch.setattr(da, "ROW_BLOCK", B)
    same = da.ragged_attention(q, wk, wv, at, 0.25, e)
    assert got.shape == (S, G, R, C) and got.dtype == jnp.float32
    top = float(jnp.abs(want).max())
    assert float(jnp.abs(got - same).max()) <= 2e-6 * top
    assert float(jnp.abs(got - want).max()) <= tol * top
    for g, w, before, new in ((gk, wk, K, kn), (gv, wv, V, vn)):
        assert g.shape == before.shape and g.dtype == before.dtype
        np.testing.assert_array_equal(_bits(g), _bits(w))
        changed = np.asarray(_bits(g) != _bits(before))
        # only entry `entry`, and there only column pos[s] of slot s
        assert not changed[[0, 2]].any()
        for s in range(S):
            assert not np.delete(changed[entry, s], pos[s], axis=1).any()
            np.testing.assert_array_equal(
                _bits(g[entry, s, :, pos[s]]), _bits(new[s]))


@pytest.mark.parametrize("L,B", WALKS)
def test_blocks_read_of_the_appended_walk_equal_a_hand_count(real_blocks,
                                                             L, B):
    pos = np.asarray([0, B - 1, B % L, L - 1, 0, (B + 5) % L])
    read, every = da.blocks_read(pos, L, da.append_block(L))
    live = [1, 1, 2, L // B, 1, 2] if L > B else [1] * 6
    assert (read, every) == (sum(live), 6 * (L // B))
    # the grid's block, unless said: what the 3-D callers count
    assert da.blocks_read(pos, L) == (
        int((pos // min(512, L) + 1).sum()), 6 * (L // min(512, L)))
    assert da.row_block(4096) == 512 and da.row_block(L) == min(512, L)


def test_the_appended_walk_inside_a_loop_writes_and_reads_every_entry():
    """The entry is a traced loop index, as in the looped family's
    decode step: pass e attends entry e with its column in place, hands
    its result on as the next pass's column, and the carried stacks end
    with every entry's column written."""
    E, S, G, C, L = 4, 3, 2, 16, 256
    rng = np.random.default_rng(3)
    K = jnp.asarray(rng.normal(size=(E, S, G * C, L)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(E, S, G * C, L)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(S, G * C)), jnp.float32)
    pos = jnp.asarray([0, 127, 200], jnp.int32)

    def one(e, carry):
        K, V, x = carry
        a, K, V = da.append_and_attend(x.reshape(S, G, 1, C), K, V, x, -x,
                                       pos, 0.25, e)
        return K, V, a.reshape(S, G * C)

    def pair(e, carry):
        K, V, x = carry
        K, V = cw.write_columns((K, V), (x, -x), pos, e)
        a = da.ragged_attention(x.reshape(S, G, 1, C), K, V, pos, 0.25, e)
        return K, V, a.reshape(S, G * C)

    got = jax.jit(lambda *c: jax.lax.fori_loop(0, E, one, c))(K, V, x)
    want = jax.jit(lambda *c: jax.lax.fori_loop(0, E, pair, c))(K, V, x)
    for g, w in zip(got, want):
        assert float(jnp.abs(g - w).max()) <= 2e-6 * float(jnp.abs(w).max())
    assert float(jnp.abs(got[0] - K).max()) > 0


@pytest.mark.parametrize("what,match", [
    ("plain rows", "stacked"), ("narrow column", "does not fit"),
    ("other dtype", "does not fit"), ("part block", "whole blocks")])
def test_append_and_attend_refuses_operands_that_do_not_fit(what, match):
    E, S, G, C, L = 2, 3, 2, 16, 256
    q = jnp.zeros((S, G, 1, C))
    K = jnp.zeros((E, S, G * C, L))
    new = jnp.zeros((S, G * C))
    pos = jnp.zeros((S,), jnp.int32)
    args = {"plain rows": (q, K[0], K[0], new, new),
            "narrow column": (q, K, K, new[:, :C], new),
            "other dtype": (q, K, K, new.astype(jnp.bfloat16), new),
            "part block": (q, K[..., :200], K[..., :200], new, new)}[what]
    with pytest.raises(ValueError, match=match):
        da.append_and_attend(*args, pos, 0.25, jnp.int32(0))


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
