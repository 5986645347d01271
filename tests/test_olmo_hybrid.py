"""The Olmo Hybrid family: the zoo model, the chunked delta rule, the
state kernel and the serving path (``serving.delta`` over a
``PagedKVCache`` of matrix state and rows) at a CPU size of one period
(3 Gated DeltaNet layers and a full one, width 64, 2 + 2 heads, keys
of 8, values of 48), float32, against the plain reference in
``tests/reference_olmo_hybrid.py``, which runs the delta rule one token
at a time."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving, tracing
from mxnet_tpu.gluon.model_zoo import olmo_hybrid as oh
from mxnet_tpu.ops.pallas import delta_step as ds
from mxnet_tpu.serving import delta, kv_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import reference_olmo_hybrid as ref                           # noqa: E402
from decode_families import tiny_olmo_hybrid                  # noqa: E402
from serial_decode import (StepCounters, check_log,           # noqa: E402
                           run_staggered, serial_transcript)

VOCAB, H, DK, DV = 97, 2, 8, 48
TOL = 2e-5      # float32 system against the float32 reference


@pytest.fixture(scope="module")
def net():
    return tiny_olmo_hybrid()


@pytest.fixture(scope="module")
def params(net):
    return oh._collect(net)


@pytest.fixture(scope="module")
def model(net):
    return serving.DecodeModel.from_block(net)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_the_tiny_size_keeps_a_period(net):
    assert net.config["kinds"] == ["linear"] * 3 + ["full"]
    assert oh.layer_kinds(8) == (["linear"] * 3 + ["full"]) * 2


def test_parameter_count_from_the_declared_shapes():
    """The published model and the 16-layer stage, from the declared
    shapes alone (nothing allocated)."""
    assert oh.get_olmo_hybrid("olmo_hybrid_7b").num_parameters() \
        == 7_430_870_688
    stage = oh.get_olmo_hybrid("olmo_hybrid_7b", num_layers=16)
    assert stage.num_parameters() == 4_100_788_944
    assert stage.config["kinds"] == (["linear"] * 3 + ["full"]) * 4
    with pytest.raises(ValueError):
        oh.get_olmo_hybrid("olmo_hybrid_8b")


@pytest.mark.parametrize("T", [1, 7, 40])
def test_forward_matches_the_reference(params, net, T):
    ids = np.random.default_rng(T).integers(0, VOCAB, T).astype(np.int32)
    got = oh.forward_logits(params, jnp.asarray(ids), net.config)
    want, _ = ref.forward(params, jnp.asarray(ids), net.config)
    assert _rel(got, want) < TOL
    # and through the block's own forward
    out = net(mx.np.array(ids[None]))
    assert _rel(out.asnumpy()[0], want) < TOL


def test_the_two_copies_of_the_reference_agree(params, net):
    from chipbench.harness import reference_olmo_hybrid as copy
    with open(ref.__file__) as a, open(copy.__file__) as b:
        assert a.read() == b.read()


def _sequential(q, k, v, g, beta):
    """The delta rule one token at a time, in numpy float64."""
    T, h, dk = q.shape
    S = np.zeros((h, dk, v.shape[-1]))
    out = []
    for t in range(T):
        S = np.exp(g[t])[:, None, None] * S
        ks = np.einsum("hd,hde->he", k[t], S)
        S = S + k[t][:, :, None] * (beta[t][:, None] * (v[t] - ks))[:, None]
        out.append(np.einsum("hd,hde->he", q[t], S))
    return np.stack(out), S


@pytest.mark.parametrize("T,dk,dv,chunk,shared", [
    (1, 16, 24, 64, False), (63, 16, 24, 64, False),
    (64, 16, 24, 64, False), (65, 16, 24, 64, False),
    (200, 16, 24, 64, False),
    (40, 16, 24, 16, False),            # the tiny size's chunk
    (200, 96, 192, 64, False), (512, 96, 192, 64, False),  # published
    (200, 16, 24, 64, True), (512, 96, 192, 64, True),
])
def test_the_chunked_rule_is_the_sequential_recurrence(T, dk, dv, chunk,
                                                       shared):
    """WY chunks against one rank-one update a token, with write
    strengths above 1 (``beta`` in (0, 2)) and strong decays among
    them, and a state that grows over several chunks.  ``shared``: every
    key leans on one direction (weight 0.99) and ``beta`` is in (1.95,
    2), so ``A``'s entries are near 2 and its powers grow as binomials:
    a form of the chunk's inverse that adds powers of ``A`` cancels them
    away in float32 and fails here, where forward substitution holds."""
    rng = np.random.default_rng(T)
    h = 3

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(T, h, dk))) / np.sqrt(dk)
    k = unit(rng.normal(size=(T, h, dk)))
    if shared:
        k = unit(0.99 * unit(rng.normal(size=(h, dk))) + 0.01 * k)
    v = rng.normal(size=(T, h, dv))
    g = -rng.exponential(0.3, size=(T, h))
    g[:, 0] *= 20                   # one head forgets fast
    beta = rng.uniform(1.95, 2.0, size=(T, h)) if shared \
        else rng.uniform(0.0, 2.0, size=(T, h))
    assert (beta > 1).any()
    o, S = oh.delta_chunked(*(jnp.asarray(a, jnp.float32)
                              for a in (q, k, v, g, beta)), chunk=chunk)
    want_o, want_S = _sequential(q, k, v, g, beta)
    assert _rel(o, want_o) < 1e-5 and _rel(S, want_S) < 1e-5


@pytest.mark.parametrize("C", [1, 2, 5, 16, 24, 64])
def test_the_chunk_inverse_is_the_inverse(C):
    """``unit_lower_inverse`` against float64's inverse of ``I + A`` for
    a chunk's ``A`` (unit keys, write strengths in (0, 2), decays), at
    the two chunks the family's sizes hold (16, 64), at sizes that are
    no power of two (the last merge's halves differ) and at 1 (no
    merge)."""
    rng = np.random.default_rng(C)
    k = rng.normal(size=(2, 3, C, 8))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = rng.uniform(0.0, 2.0, size=(2, 3, C))
    dec = np.cumsum(-rng.exponential(0.1, size=(2, 3, C)), axis=-1)
    A = np.tril(beta[..., None] * np.einsum("...td,...jd->...tj", k, k)
                * np.exp(dec[..., :, None] - dec[..., None, :]), -1)
    got = oh.unit_lower_inverse(jnp.asarray(A, jnp.float32))
    assert got.shape == A.shape and got.dtype == jnp.float32
    assert _rel(got, np.linalg.inv(np.eye(C) + A)) < 1e-5


def _primitives(jaxpr):
    """The names of every primitive in ``jaxpr``, sub-jaxprs too."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


@pytest.mark.parametrize("chunk", [16, 64])
def test_the_chunked_rule_calls_no_triangular_solve(chunk):
    """The chunk's writes come from products (``unit_lower_inverse``),
    not from ``lax.linalg.triangular_solve``, which the TPU compiler
    expands into a sequential ``InvertDiagBlocksLowerTriangular``; the
    v5e compile at the cell's shape is in tests/test_flash_attention.py."""
    T, h, dk, dv = 100, 2, 8, 48
    args = [jnp.zeros((T, h, d), jnp.float32) for d in (dk, dk, dv)]
    args += [jnp.zeros((T, h), jnp.float32)] * 2
    jaxpr = jax.make_jaxpr(lambda *a: oh.delta_chunked(*a, chunk=chunk))(
        *args)
    names = _primitives(jaxpr.jaxpr)
    assert "dot_general" in names and "triangular_solve" not in names


def test_the_prefill_program_calls_no_triangular_solve(model):
    toks = jnp.zeros(64, jnp.int32)
    jaxpr = jax.make_jaxpr(model._prefill_fn)(model.params, toks,
                                              jnp.int32(37))
    names = _primitives(jaxpr.jaxpr)
    assert "dot_general" in names and "triangular_solve" not in names


def test_padding_past_the_real_length_leaves_the_state(params, net):
    """A prompt of 37 padded to 64 and to 128 installs the same state
    and conv inputs, and the reference's at 37."""
    cfg = net.config
    ids = np.random.default_rng(3).integers(0, VOCAB, 37).astype(np.int32)
    _, want = ref.forward(params, jnp.asarray(ids), cfg)
    for Lp in (64, 128):
        padded = np.zeros(Lp, np.int32)
        padded[:37] = ids
        _, held = oh.forward_sequence(params, jnp.asarray(padded), 37, cfg)
        for kind, got, w in zip(cfg["kinds"], held, want):
            if kind == "linear":
                assert _rel(got[0], w[0]) < TOL and _rel(got[1], w[1]) < TOL


def _step_ref(S, q, k, v, a, b):
    S = a[..., None, None] * S
    ks = np.einsum("shd,shde->she", k, S)
    S = S + k[..., None] * (b[..., None] * (v - ks))[..., None, :]
    return np.einsum("shd,shde->she", q, S), S


@pytest.mark.parametrize("shape", [(3, 2, 8, 48), (2, 6, 16, 192),
                                   (2, 4, 8, 64)])
def test_the_state_kernel_is_the_step_in_place(shape):
    """Interpret mode against the recurrence's step in numpy, at value
    widths that are and are not whole lane tiles; under ``jit`` with the
    state donated the new state IS the old buffer."""
    Sl, h, dk, dv = shape
    rng = np.random.default_rng(1)
    S = rng.normal(size=(Sl, h, dk, dv)).astype(np.float32)
    q, k = (rng.normal(size=(Sl, h, dk)).astype(np.float32)
            for _ in range(2))
    v = rng.normal(size=(Sl, h, dv)).astype(np.float32)
    a = rng.uniform(size=(Sl, h)).astype(np.float32)
    b = rng.uniform(0, 2, size=(Sl, h)).astype(np.float32)
    want_o, want_S = _step_ref(S, q, k, v, a, b)
    lanes = jax.device_put(ds.to_lanes(jnp.asarray(S)))
    step = jax.jit(ds.delta_step, donate_argnums=(0,))
    text = step.lower(lanes, q, k, v, a, b).as_text()
    assert "input_output_aliases" in text or "tf.aliasing_output" in text
    before = lanes.unsafe_buffer_pointer()
    o, new = step(lanes, q, k, v, a, b)
    assert new.unsafe_buffer_pointer() == before and lanes.is_deleted()
    assert _rel(o, want_o) < 1e-5
    assert _rel(ds.from_lanes(new, h), want_S) < 1e-5


def test_a_block_is_whole_units_of_lane_tiles():
    # published: 30 heads of 96 x 192, two heads a unit (384 lanes), 10
    # heads a grid step (737 KB)
    assert ds.unit_heads(192) == 2 and ds.block_heads(30, 96, 192) == 10
    assert ds.unit_heads(128) == 1 and ds.unit_heads(48) == 8
    # no unit divides 2 heads of 48: the block is every head
    assert ds.block_heads(2, 8, 48) == 2


def test_the_cache_holds_both_kinds_at_their_sizes(model):
    cache = model.make_cache(3, (64, 128))
    assert cache.kinds == ("state",) * 3 + ("rows",)
    per = cache.bytes_by_kind()
    conv = 2 * H * DK + H * DV
    assert per["state"] == 3 * 3 * 4 * (DK * H * DV + 3 * conv)
    assert per["rows"] == 3 * 64 * 2 * 64 * 4
    assert [a.shape for a in cache.state["ssm"]] == [(3, DK, H * DV)] * 3
    assert [a.shape for a in cache.state["conv"]] == [(3, 3, conv)] * 3


def test_a_slot_of_the_published_stage_at_2048_positions():
    """The cell's arithmetic from the cache itself: 12 x 2,350,080 B of
    state and 4 x 15,360 B a position of rows."""
    stage = oh.get_olmo_hybrid("olmo_hybrid_7b", num_layers=16,
                               dtype="bfloat16")
    cfg = stage.config
    fake = delta.DeltaDecodeModel.__new__(delta.DeltaDecodeModel)
    fake.cfg = cfg
    cache = kv_cache.PagedKVCache(
        16, 30, 128, 1, buckets=(2048,), dtype=jnp.bfloat16,
        kinds=[delta.CACHE_KIND[k] for k in cfg["kinds"]],
        state_shapes=fake.state_shapes())
    per = cache.bytes_by_kind()
    assert per["state"] == 12 * 2_350_080
    assert per["rows"] == 4 * 15_360 * 2048
    assert per["state"] + per["rows"] == 154_030_080


def _greedy(model, prompt, n, **kw):
    engine = serving.GenerationEngine(
        model, **dict(dict(max_slots=3, kv_buckets=(64, 128),
                           prefix_slots=0, max_tokens=64), **kw))
    stream = engine.submit(prompt, max_new_tokens=n, method="greedy")
    while engine.run_iteration():
        pass
    return engine, np.asarray(stream.result())


def test_prefill_then_decode_continues_the_reference(model, params, net):
    """Prefill, then greedy decoding through the engine: every token is
    the reference's argmax over the sequence so far.  The same sequence
    fed to the decode program directly, across the 64 bucket into 128:
    the state, conv inputs and rows the slot is left with are the
    reference's over it."""
    cfg = net.config
    prompt = np.random.default_rng(11).integers(0, VOCAB, 45) \
        .astype(np.int32)
    _, out = _greedy(model, prompt, 30)
    seq = np.concatenate([prompt, out[:-1]])
    want, held = ref.forward(params, jnp.asarray(seq), cfg)
    assert (np.asarray(want)[44:].argmax(-1) == out).all()
    logits, ks, vs, state = model.prefill(prompt, 64)
    assert _rel(logits, want[44]) < TOL
    cache = model.make_cache(1, (64, 128))
    cache.write_prompt(0, ks, vs, len(prompt), state=state)
    for tok in out[:-1]:
        cache.ensure_capacity(cache.needed_capacity())
        model.step(cache, np.array([tok], np.int32), cache.positions)
        cache.positions += 1
    assert cache.bucket == 128
    n = len(seq)
    lin = [i for i, k in enumerate(cfg["kinds"]) if k == "linear"]
    for j, i in enumerate(lin):
        assert _rel(ds.from_lanes(cache.state["ssm"][j][0], H),
                    held[i][1]) < 1e-4
        assert _rel(cache.state["conv"][j][0], held[i][0]) < 1e-4
    for got, w in ((cache.k(0), held[3][0]), (cache.v(0), held[3][1])):
        assert _rel(np.asarray(got[0]).T[:n],
                    np.asarray(w).reshape(n, -1)) < 1e-4


def test_alone_equals_in_a_full_batch(model):
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, VOCAB, 20).astype(np.int32)
    _, alone = _greedy(model, prompt, 16)
    engine = serving.GenerationEngine(model, max_slots=3,
                                      kv_buckets=(64, 128), prefix_slots=0,
                                      max_tokens=64)
    streams = [engine.submit(p, max_new_tokens=16, method="greedy")
               for p in [prompt] + [rng.integers(0, VOCAB, n)
                                    .astype(np.int32) for n in (5, 50)]]
    while engine.run_iteration():
        pass
    assert (np.asarray(streams[0].result()) == alone).all()


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


DELTA_MIX = [
    dict(prompt=_prompt(11, 50), max_new_tokens=30, at=0),
    dict(prompt=_prompt(3, 51), max_new_tokens=6, at=0),
    dict(prompt=_prompt(20, 52), max_new_tokens=12, at=2),
    dict(prompt=_prompt(5, 53), max_new_tokens=4, at=5),
    dict(prompt=_prompt(40, 54), max_new_tokens=30, at=26),
]
LANES = [dict(method="top_k", top_k=7, temperature=0.8, seed=11),
         dict(method="greedy"),
         dict(method="sample", temperature=1.3, seed=2 ** 31 - 5),
         dict(method="top_p", top_p=0.85, seed=3),
         dict(method="top_k", top_k=3, seed=4)]


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_one_step_in_flight_changes_no_transcript(model, sampled):
    """The engine launches step N+1 from step N's token array before it
    reads N back, and queues admissions behind it; every stream still
    equals the request decoded alone by ``model.step``,
    launch-wait-read: matrix state, conv inputs and rows, greedy and
    with sampling lanes in the same batch."""
    mix = [dict(r, **(lane if sampled else {}))
           for r, lane in zip(DELTA_MIX, LANES)]
    engine = serving.GenerationEngine(model, max_slots=2,
                                      kv_buckets=(64, 128), prefix_slots=0,
                                      max_tokens=64)
    counted = StepCounters()
    streams = run_staggered(engine, mix)
    moved = counted.moved()
    for s, r in zip(streams, mix):
        kw = {k: v for k, v in r.items() if k not in ("at", "prompt")}
        assert (s.result(), s.finish_reason) == serial_transcript(
            model, engine, r["prompt"], **kw)
    assert moved["ahead"] >= 25 and moved["discarded"] == 0
    assert moved["admissions_ahead"] >= 1
    assert moved["admit"] == moved["finish"] == 0
    assert moved["ahead"] + counted.fallbacks() == moved["iterations"]
    check_log(engine, streams)


def test_the_decode_step_calls_the_kernels_once_a_layer(model):
    """One ``delta_step`` a linear layer, one column write and one
    ragged read a full layer, and the programs in the table under the
    family's name."""
    engine = serving.GenerationEngine(model, max_slots=2, kv_buckets=(64,),
                                      prefix_slots=0, max_tokens=8)
    engine.warmup()
    step = next(p for p in tracing.programs()
                if p.role == "decode" and p.family == "olmo_hybrid")
    text = step.hlo_text()
    assert text.count('"delta_step"') + text.count("delta_step") >= 1
    jaxpr = jax.make_jaxpr(model._step_fn)(
        model.params, *engine.cache.buffers(), np.zeros(2, np.int32),
        np.zeros(2, np.int32), *model.greedy_sampling(2))
    names = [e.params.get("name") for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    names += [e.params.get("name") for eqn in jaxpr.jaxpr.eqns
              for sub in jax.core.jaxprs_in_params(eqn.params)
              for e in sub.eqns if e.primitive.name == "pallas_call"]
    assert names.count("delta_step") == 3
    assert names.count("ragged_attention") == 1
    assert {p.role for p in tracing.programs()
            if p.family == "olmo_hybrid"} >= {"decode", "prefill", "select"}


def test_from_block_picks_the_family(model):
    assert isinstance(model, delta.DeltaDecodeModel)
    d = model.describe()
    assert d["family"] == "olmo_hybrid" and d["layer_kinds"] == {
        "linear": 3, "full": 1}
    assert (d["lin_heads"], d["key_dim"], d["value_dim"]) == (H, DK, DV)
