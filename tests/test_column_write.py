"""The column-write kernel (ops/pallas/column_write.py) against the
per-slot ``dynamic_update_slice`` loop it replaced in the hybrid and the
sparse-expert decode steps: bitwise, over the whole buffer.  Interpret
mode on the CPU; its v5e compiles are in tests/test_flash_attention.py."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax import lax

from mxnet_tpu.ops.pallas import column_write as cw

CHANNELS = 16


def loop_of_updates(buf, cols, at):
    """The plain reference: one in-place update a slot, as the decode
    steps wrote their columns before the kernel (and as the GPT
    family's ``serving.gpt._slot_block_step`` still does)."""
    for i in range(buf.shape[0]):
        buf = lax.dynamic_update_slice(
            buf, lax.slice_in_dim(cols, i, i + 1),
            (i, 0, lax.index_in_dim(at, i, keepdims=False)),
            allow_negative_indices=False)
    return buf


def _bits(a):
    a = onp.asarray(a)
    return a.view({2: onp.uint16, 4: onp.uint32}[a.dtype.itemsize])


def _random_bits(rng, shape, dtype):
    """Every bit pattern of the dtype, NaNs, infinities and subnormals
    among them: a write is a move and must not look at the values."""
    word = {2: onp.uint16, 4: onp.uint32}[jnp.dtype(dtype).itemsize]
    raw = rng.integers(0, onp.iinfo(word).max, size=shape, dtype=word,
                       endpoint=True)
    return lax.bitcast_convert_type(jnp.asarray(raw), dtype)


def _positions(which, S, L, rng):
    if which == "edges":
        # the first and the last lane of a tile, the buffer's last
        # column, and past it: a position beyond L - 1 lands on L - 1
        edges = [0, 127, 128, L - 1, L, L + 7, 1, L // 2]
        return onp.array([edges[i % len(edges)] for i in range(S)])
    if which == "ring":
        # a ring of L columns after it has wrapped: position p lives in
        # column p % L
        return (L + rng.integers(0, 3 * L, size=S)) % L
    if which == "same":
        return onp.full(S, 129 % L)
    return rng.integers(0, L, size=S)


@pytest.mark.parametrize("which", ["edges", "ring", "same", "random"])
@pytest.mark.parametrize("S", [3, 48, 64])
@pytest.mark.parametrize("L", [64, 512, 4096])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_kernel_is_the_loop_of_updates_bit_for_bit(dtype, L, S, which):
    """K and V through one call; every element of both buffers equal to
    what one ``dynamic_update_slice`` a slot leaves."""
    rng = onp.random.default_rng(S * L + len(which))
    at = jnp.asarray(_positions(which, S, L, rng), jnp.int32)
    shape = (S, CHANNELS, L)
    kb, vb = _random_bits(rng, shape, dtype), _random_bits(rng, shape, dtype)
    kc = _random_bits(rng, (S, CHANNELS, 1), dtype)
    vc = _random_bits(rng, (S, CHANNELS, 1), dtype)
    got_k, got_v = jax.jit(cw.write_columns)((kb, vb), (kc, vc), at)
    want = jax.jit(loop_of_updates)
    assert got_k.dtype == kb.dtype and got_k.shape == kb.shape
    onp.testing.assert_array_equal(_bits(got_k), _bits(want(kb, kc, at)))
    onp.testing.assert_array_equal(_bits(got_v), _bits(want(vb, vc, at)))


@pytest.mark.parametrize("S,C,L", [(3, 16, 64), (5, 32, 512), (8, 16, 4096)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_only_one_column_a_slot_changes(dtype, S, C, L):
    """A buffer full of a sentinel: after the write exactly ``S``
    columns differ from it, slot s's at ``at[s]``, and they hold the
    new values; buffers of different widths share a call."""
    rng = onp.random.default_rng(L)
    at = rng.integers(0, L, size=S)
    sentinel = jnp.full((S, C, L), -7.0, dtype)
    wide = jnp.full((S, 2 * C, L), -7.0, dtype)
    cols = jnp.asarray(rng.standard_normal((S, C, 1)) + 10.0, dtype)
    wide_cols = jnp.asarray(rng.standard_normal((S, 2 * C, 1)) + 10.0,
                            dtype)
    out, wide_out = cw.write_columns((sentinel, wide), (cols, wide_cols),
                                     jnp.asarray(at, jnp.int32))
    for got, new in ((out, cols), (wide_out, wide_cols)):
        changed = onp.asarray(got != -7.0)
        assert changed.sum() == S * new.shape[1]
        for s in range(S):
            assert changed[s, :, at[s]].all()
            onp.testing.assert_array_equal(
                onp.asarray(got[s, :, at[s]], onp.float32),
                onp.asarray(new[s, :, 0], onp.float32))


def test_one_buffer_and_columns_without_the_unit_axis():
    """One buffer alone is a call too, and ``(S, C)`` columns are taken
    as ``(S, C, 1)`` are."""
    rng = onp.random.default_rng(0)
    buf = _random_bits(rng, (4, 16, 256), jnp.bfloat16)
    cols = _random_bits(rng, (4, 16), jnp.bfloat16)
    at = jnp.asarray([0, 255, 128, 127], jnp.int32)
    (got,) = cw.write_columns((buf,), (cols,), at)
    onp.testing.assert_array_equal(
        _bits(got), _bits(loop_of_updates(buf, cols[:, :, None], at)))


@pytest.mark.parametrize("bufs,cols", [
    (((4, 16, 256), (4, 16, 512)), ((4, 16, 1), (4, 16, 1))),   # two lengths
    (((4, 16, 256),), ((4, 8, 1),)),                            # channels
    (((4, 16, 256),), ((3, 16, 1),)),                           # slots
    (((4, 16, 200),), ((4, 16, 1),)),                           # part block
], ids=["lengths", "channels", "slots", "part-block"])
def test_operands_that_do_not_fit_are_refused_by_name(bufs, cols):
    at = jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match="does not fit|whole blocks"):
        cw.write_columns([jnp.zeros(s, jnp.float32) for s in bufs],
                         [jnp.zeros(s, jnp.float32) for s in cols], at)


def test_a_dtype_that_does_not_pack_is_refused():
    with pytest.raises(ValueError, match="do not pack"):
        cw.write_columns((jnp.zeros((2, 3, 128), jnp.bfloat16),),
                         (jnp.zeros((2, 3, 1), jnp.bfloat16),),
                         jnp.zeros((2,), jnp.int32))


# ---------------------------------------------------------------------------
# the stacked form (a looped family's cache: one entry a pass, written
# from inside a loop with the entry's index scalar-prefetched)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("entry", [0, 2, 4])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_the_stacked_form_equals_the_plain_form_entry_by_entry(dtype, entry):
    """Entry ``entry`` of the stack comes out as the plain form leaves
    that entry alone, bit for bit; every other entry is untouched."""
    E, S, L = 5, 3, 256
    rng = onp.random.default_rng(entry)
    at = jnp.asarray([0, 130, L - 1], jnp.int32)
    # plain values in the buffers: interpreted on the CPU, the
    # unvisited entries of a bfloat16 stack come back with their
    # subnormal bit patterns changed (0.4 % of random bits, the
    # subnormals' share; float32 keeps every bit).  The chip aliases the
    # stack and moves nothing of them
    kb = jnp.asarray(rng.normal(size=(E, S, CHANNELS, L)), dtype)
    vb = jnp.asarray(rng.normal(size=(E, S, CHANNELS, L)), dtype)
    kc = _random_bits(rng, (S, CHANNELS), dtype)
    vc = _random_bits(rng, (S, CHANNELS), dtype)
    got_k, got_v = jax.jit(cw.write_columns)((kb, vb), (kc, vc), at,
                                             jnp.int32(entry))
    want_k, want_v = cw.write_columns((kb[entry], vb[entry]), (kc, vc), at)
    for got, want, before in ((got_k, want_k, kb), (got_v, want_v, vb)):
        assert got.shape == before.shape and got.dtype == before.dtype
        onp.testing.assert_array_equal(_bits(got[entry]), _bits(want))
        others = [e for e in range(E) if e != entry]
        onp.testing.assert_array_equal(_bits(got)[others],
                                       _bits(before)[others])


def test_the_stacked_form_inside_a_loop_writes_every_entry():
    """The entry is a traced loop index: each pass writes its own entry
    of the carried stack, as the looped family's decode step does."""
    E, S, L = 4, 2, 128
    rng = onp.random.default_rng(1)
    buf = _random_bits(rng, (E, S, CHANNELS, L), jnp.float32)
    cols = jnp.asarray(rng.normal(size=(E, S, CHANNELS)), jnp.float32)
    at = jnp.asarray([5, 127], jnp.int32)

    def program(buf):
        return lax.fori_loop(
            0, E, lambda e, b: cw.write_columns((b,), (cols[e],), at, e)[0],
            buf)

    got = jax.jit(program)(buf)
    want = onp.asarray(buf).copy()
    for s in range(S):
        want[:, s, :, int(at[s])] = onp.asarray(cols)[:, s]
    onp.testing.assert_array_equal(_bits(got), _bits(want))


def test_a_stack_without_its_entry_is_refused():
    buf = jnp.zeros((2, 3, CHANNELS, 128))
    col = jnp.zeros((3, CHANNELS))
    with pytest.raises(ValueError, match="stacked"):
        cw.write_columns((buf,), (col,), jnp.zeros(3, jnp.int32))
    with pytest.raises(ValueError, match="stacked"):
        cw.write_columns((buf[0],), (col,), jnp.zeros(3, jnp.int32),
                         jnp.int32(0))


# ---------------------------------------------------------------------------
# the same move inside the looped family's one call a pass
# (decode_attention.append_and_attend): the written stacks against this
# kernel's; the attention it returns is tests/test_decode_attention.py's
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("entry", [0, 3])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_the_appended_walk_moves_the_column_as_this_kernel_does(dtype,
                                                                entry):
    """Columns of every bit pattern (NaNs, infinities, subnormals)
    through ``append_and_attend``: both stacks come out bit for bit as
    ``write_columns`` leaves them, first and last lane of a tile, the
    rows' last column and a position past it (clamped) among them."""
    from mxnet_tpu.ops.pallas import decode_attention as da
    E, S, G, C, L = 4, 5, 2, CHANNELS // 2, 1024
    rng = onp.random.default_rng(entry + 7)
    at = jnp.asarray([0, 127, 128, L - 1, L + 9], jnp.int32)
    kb = jnp.asarray(rng.normal(size=(E, S, CHANNELS, L)), dtype)
    vb = jnp.asarray(rng.normal(size=(E, S, CHANNELS, L)), dtype)
    kc = _random_bits(rng, (S, CHANNELS), dtype)
    vc = _random_bits(rng, (S, CHANNELS), dtype)
    q = jnp.zeros((S, G, 1, C), dtype)
    _, got_k, got_v = jax.jit(da.append_and_attend, static_argnums=6)(
        q, kb, vb, kc, vc, at, 1.0, jnp.int32(entry))
    want_k, want_v = cw.write_columns((kb, vb), (kc, vc), at,
                                      jnp.int32(entry))
    onp.testing.assert_array_equal(_bits(got_k), _bits(want_k))
    onp.testing.assert_array_equal(_bits(got_v), _bits(want_v))
