"""Pallas flash-attention kernels vs the dense reference (interpret mode
on CPU — the kernels themselves, not just the dispatch heuristics)."""
import re

import numpy as onp
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops.pallas.attention import (_dense_reference, _flash2,
                                            flash_attention, head_group)


def _flash_bhtd(q, k, v, bias, seed, rate, scale, causal, block_q, block_k,
                bias_grad=True):
    """The kernels with explicit blocks over (B, H, T, D) operands, the
    layout ``_dense_reference`` and most cases of this file state theirs
    in (the kernels' own is (B, T, H·D))."""
    H, D = q.shape[1], q.shape[3]

    def fold(a):
        return jnp.swapaxes(a, 1, 2).reshape(a.shape[0], a.shape[2], H * D)

    out = _flash2(fold(q), fold(k), fold(v), bias, seed, rate, scale,
                  causal, block_q, block_k, bias_grad, H)
    return jnp.swapaxes(out.reshape(out.shape[0], out.shape[1], H, D), 1, 2)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(1, 2, 128, 64), (2, 3, 200, 32)])
def test_flash_forward_matches_dense(causal, shape):
    B, H, T, D = shape
    rng = onp.random.RandomState(0)
    q = jnp.asarray(rng.normal(0, 1, shape).astype("float32"))
    k = jnp.asarray(rng.normal(0, 1, shape).astype("float32"))
    v = jnp.asarray(rng.normal(0, 1, shape).astype("float32"))
    scale = 1.0 / D ** 0.5
    out = _flash_bhtd(q, k, v, None, None, 0.0, scale, causal, 128, 128)
    ref = _dense_reference(q, k, v, scale, causal)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense(causal):
    B, H, T, D = 1, 2, 160, 32   # off-block-size T exercises padding
    rng = onp.random.RandomState(1)
    q = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype("float32"))
    k = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype("float32"))
    v = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype("float32"))
    scale = 1.0 / D ** 0.5

    def loss_flash(q, k, v):
        return jnp.sum(_flash_bhtd(q, k, v, None, None, 0.0, scale,
                       causal, 128, 128) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_reference(q, k, v, scale, causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    # rtol accommodates chip f32 rounding at causal mask boundaries
    # (single-element ~2e-3 deviations on the real TPU)
    for a, b, name in zip(gf, gd, "qkv"):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=3e-3, atol=1e-4,
                                    err_msg=f"d{name}")


def test_flash_public_entry_bf16():
    # public entry uses the jax (B, T, H, D) layout
    B, T, H, D = 1, 256, 2, 64
    rng = onp.random.RandomState(2)
    q = jnp.asarray(rng.normal(0, 1, (B, T, H, D))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.normal(0, 1, (B, T, H, D))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(0, 1, (B, T, H, D))).astype(jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = jnp.swapaxes(_dense_reference(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(v, 1, 2), 1.0 / D ** 0.5, True), 1, 2)
    assert out.dtype == jnp.bfloat16
    onp.testing.assert_allclose(
        onp.asarray(out).astype("float32"),
        onp.asarray(ref).astype("float32"), rtol=5e-2, atol=5e-2)


def test_flash_bias_matches_dense():
    """Additive bias streams through the kernel; fwd+bwd must match the
    dense reference including the bias gradient."""
    rng = onp.random.RandomState(3)
    B, H, T, D = 2, 2, 64, 16
    q = jnp.asarray(rng.uniform(-1, 1, (B, H, T, D)).astype("float32"))
    k = jnp.asarray(rng.uniform(-1, 1, (B, H, T, D)).astype("float32"))
    v = jnp.asarray(rng.uniform(-1, 1, (B, H, T, D)).astype("float32"))
    bias = jnp.asarray(rng.uniform(-2, 2, (B, H, T, T)).astype("float32"))
    scale = 1.0 / onp.sqrt(D)

    def loss_flash(q, k, v, bias):
        return jnp.sum(_flash_bhtd(q, k, v, bias, None, 0.0, scale, False,
                               32, 32) ** 2)

    def loss_dense(q, k, v, bias):
        return jnp.sum(_dense_reference(q, k, v, scale, False,
                                        bias=bias) ** 2)

    out_f = _flash_bhtd(q, k, v, bias, None, 0.0, scale, False, 32, 32)
    out_d = _dense_reference(q, k, v, scale, False, bias=bias)
    onp.testing.assert_allclose(onp.asarray(out_f), onp.asarray(out_d),
                                rtol=2e-4, atol=2e-5)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, b in zip(gf, gd):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=3e-4, atol=3e-5)


def test_flash_broadcast_bias_grad():
    """(1,1,Tq,Tk) broadcast bias: gradient reduces over batch+heads."""
    rng = onp.random.RandomState(4)
    B, H, T, D = 2, 3, 32, 8
    q = jnp.asarray(rng.uniform(-1, 1, (B, H, T, D)).astype("float32"))
    bias = jnp.asarray(rng.uniform(-1, 1, (1, 1, T, T)).astype("float32"))
    scale = 1.0 / onp.sqrt(D)

    def loss_flash(bias):
        return jnp.sum(_flash_bhtd(q, q, q, bias, None, 0.0, scale, True,
                               16, 16) ** 2)

    def loss_dense(bias):
        return jnp.sum(_dense_reference(q, q, q, scale, True,
                                        bias=bias) ** 2)

    gf = jax.grad(loss_flash)(bias)
    gd = jax.grad(loss_dense)(bias)
    assert gf.shape == bias.shape
    onp.testing.assert_allclose(onp.asarray(gf), onp.asarray(gd),
                                rtol=3e-4, atol=3e-5)


def test_flash_dropout_semantics_cpu():
    """On CPU dropout takes the dense XLA fallback: zero-rate equals the
    no-dropout path; nonzero rate keeps the expected row normalization
    and zeros ~rate of the weights."""
    from mxnet_tpu.ops.pallas.attention import flash_attention
    rng = onp.random.RandomState(5)
    B, T, H, D = 2, 32, 2, 8
    q = jnp.asarray(rng.uniform(-1, 1, (B, T, H, D)).astype("float32"))
    seed = jnp.asarray([123, 456], jnp.int32)
    out0 = flash_attention(q, q, q)
    out_d = flash_attention(q, q, q, dropout=0.5, dropout_seed=seed)
    assert out_d.shape == out0.shape
    assert bool(jnp.isfinite(out_d).all())
    # dropped attention changes the output but keeps its scale
    diff = float(jnp.abs(out_d - out0).mean())
    assert diff > 1e-4
    assert float(jnp.abs(out_d).mean()) < 4 * float(jnp.abs(out0).mean())
    # missing seed errors
    import pytest as _pytest
    with _pytest.raises(ValueError):
        flash_attention(q, q, q, dropout=0.5)


def test_flash_tunable_blocks():
    rng = onp.random.RandomState(6)
    q = jnp.asarray(rng.uniform(-1, 1, (1, 2, 96, 16)).astype("float32"))
    scale = 0.25
    o1 = _flash_bhtd(q, q, q, None, None, 0.0, scale, False, 32, 48)
    o2 = _flash_bhtd(q, q, q, None, None, 0.0, scale, False, 96, 96)
    onp.testing.assert_allclose(onp.asarray(o1), onp.asarray(o2),
                                rtol=2e-4, atol=2e-5)


def test_flash_key_padding_row_bias():
    """(B,1,1,Tk) Tq-broadcast row bias — the canonical BERT key-padding
    mask — streams as (1, block_k) rows (r3): fwd + q/k/v grads must
    match dense, including a PADDED kv range and off-block T."""
    rng = onp.random.RandomState(5)
    B, H, T, D = 2, 2, 96, 16           # T=96 pads inside 32-blocks
    q = jnp.asarray(rng.uniform(-1, 1, (B, H, T, D)).astype("float32"))
    k = jnp.asarray(rng.uniform(-1, 1, (B, H, T, D)).astype("float32"))
    v = jnp.asarray(rng.uniform(-1, 1, (B, H, T, D)).astype("float32"))
    # boolean keep-mask -> additive -inf-ish rows; last 20 keys padded out
    keep = onp.ones((B, 1, 1, T), bool)
    keep[:, :, :, -20:] = False
    bias = jnp.asarray(onp.where(keep, 0.0, -1e9).astype("float32"))
    scale = 1.0 / onp.sqrt(D)

    def loss_flash(q, k, v):
        return jnp.sum(_flash_bhtd(q, k, v, bias, None, 0.0, scale, False,
                               32, 32, False) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_reference(q, k, v, scale, False,
                                        bias=bias) ** 2)

    out_f = _flash_bhtd(q, k, v, bias, None, 0.0, scale, False, 32, 32, False)
    out_d = _dense_reference(q, k, v, scale, False, bias=bias)
    onp.testing.assert_allclose(onp.asarray(out_f), onp.asarray(out_d),
                                rtol=2e-4, atol=2e-5)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=3e-4, atol=3e-5)


def test_flash_row_bias_learned_grad():
    """A LEARNED (B,1,1,Tk) row bias gets its gradient reduced over the
    query axis as well as the broadcast head axis."""
    rng = onp.random.RandomState(6)
    B, H, T, D = 2, 2, 32, 8
    q = jnp.asarray(rng.uniform(-1, 1, (B, H, T, D)).astype("float32"))
    bias = jnp.asarray(rng.uniform(-1, 1, (B, 1, 1, T)).astype("float32"))
    scale = 1.0 / onp.sqrt(D)

    def loss_flash(bias):
        return jnp.sum(_flash_bhtd(q, q, q, bias, None, 0.0, scale, False,
                               16, 16) ** 2)

    def loss_dense(bias):
        return jnp.sum(_dense_reference(q, q, q, scale, False,
                                        bias=bias) ** 2)

    gf = jax.grad(loss_flash)(bias)
    gd = jax.grad(loss_dense)(bias)
    assert gf.shape == bias.shape
    onp.testing.assert_allclose(onp.asarray(gf), onp.asarray(gd),
                                rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_backward_matches_twopass_and_dense(causal):
    """r5 fused single-pass backward (n_k == 1: the whole K in one
    block) must produce the same grads as the two-pass dq/dkv recipe
    (forced via small k blocks) and the dense reference."""
    B, H, T, D = 2, 2, 160, 32     # off-block T exercises padding
    rng = onp.random.RandomState(5)
    q = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype("float32"))
    k = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype("float32"))
    v = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype("float32"))
    scale = 1.0 / D ** 0.5

    def loss(fn):
        return jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) ** 2), argnums=(0, 1, 2))

    # block_k=256 >= T -> fused; block_k=64 -> two-pass (n_k=3)
    gf = loss(lambda q, k, v: _flash_bhtd(q, k, v, None, None, 0.0, scale,
                                      causal, 64, 256))(q, k, v)
    gt = loss(lambda q, k, v: _flash_bhtd(q, k, v, None, None, 0.0, scale,
                                      causal, 64, 64))(q, k, v)
    gd = loss(lambda q, k, v: _dense_reference(q, k, v, scale,
                                               causal))(q, k, v)
    for a, b in zip(gf, gt):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-4, atol=2e-5)
    # vs dense: looser — on-chip XLA reduces in a different order than
    # the blockwise kernel (observed max |diff| ~1.5e-4 on f32 grads)
    for a, b in zip(gf, gd):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=5e-4, atol=5e-4)


def test_fused_backward_bias_grad_matches_dense():
    """Learned-bias ds emission on the fused path: d_bias (including
    broadcast-dim reduction) matches dense autodiff."""
    B, H, T, D = 2, 2, 96, 16
    rng = onp.random.RandomState(9)
    q = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype("float32"))
    bias = jnp.asarray(rng.normal(0, 1, (1, H, T, T)).astype("float32"))
    scale = 1.0 / D ** 0.5

    gf = jax.grad(lambda b_: jnp.sum(
        _flash_bhtd(q, q, q, b_, None, 0.0, scale, False, 48, 128) ** 2))(bias)
    gd = jax.grad(lambda b_: jnp.sum(
        _dense_reference(q, q, q, scale, False, b_) ** 2))(bias)
    onp.testing.assert_allclose(onp.asarray(gf), onp.asarray(gd),
                                rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("heads,dim,group", [
    (16, 64, 2), (20, 64, 2), (3, 64, 3), (16, 128, 1), (128, 128, 1),
    (12, 32, 4), (2, 8, 2)])
def test_head_group_is_the_fewest_heads_that_fill_whole_lane_tiles(
        heads, dim, group):
    assert head_group(heads, dim) == group


@pytest.mark.parametrize("T", [128, 512])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("heads,dim", [(16, 64), (20, 64), (3, 64),
                                       (16, 128)])
def test_projection_layout_matches_dense(heads, dim, dtype, causal, T):
    """The kernels fed as a model feeds them — (B, T, H·D) projections
    seen as (B, T, H, D), a (B, 1, 1, Tk) key-padding bias — against the
    dense reference, values and ``jax.grad``: pairs of 64-wide heads,
    single 128-wide heads and a head count with no pair (one group of
    all three)."""
    B = 2
    rng = onp.random.RandomState(heads * dim + T)
    q, k, v, w = (jnp.asarray(rng.normal(0, 1, (B, T, heads * dim)), dtype)
                  for _ in range(4))
    keep = onp.ones((B, 1, 1, T), bool)
    keep[1, :, :, -T // 4:] = False             # one row's last quarter
    bias = jnp.asarray(onp.where(keep, 0.0, -1e9), dtype)

    def split(a):
        return a.reshape(B, T, heads, dim)

    def flash(q, k, v):
        return flash_attention(split(q), split(k), split(v), causal=causal,
                               bias=bias, bias_grad=False
                               ).reshape(B, T, heads * dim)

    def dense(q, k, v):
        t = lambda a: jnp.swapaxes(split(a), 1, 2)      # noqa: E731
        out = _dense_reference(t(q), t(k), t(v), dim ** -0.5, causal, bias)
        return jnp.swapaxes(out, 1, 2).reshape(B, T, heads * dim)

    def vg(f):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: (f(q, k, v).astype(jnp.float32)
                             * w.astype(jnp.float32)).sum(),
            argnums=(0, 1, 2)))

    # the file's own tolerances: float32 as the dense-parity cases above,
    # bfloat16 as the public entry's
    tol = (dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16
           else dict(rtol=3e-3, atol=1e-4))
    as32 = lambda a: onp.asarray(a.astype(jnp.float32))  # noqa: E731
    onp.testing.assert_allclose(as32(flash(q, k, v)), as32(dense(q, k, v)),
                                **tol)
    (_, gf), (_, gd) = vg(flash)(q, k, v), vg(dense)(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        assert a.shape == (B, T, heads * dim) and a.dtype == dtype
        onp.testing.assert_allclose(as32(a), as32(b), err_msg=f"d{name}",
                                    **tol)


# ---------------------------------------------------------------------------
# Chip-free compile coverage: AOT-compile the kernels under Mosaic for a
# v5e topology (no TPU attached).  The CPU suite above runs interpret
# mode, so only these see the real lowering — VMEM fit, tiling legality,
# and "Mosaic kernels cannot be automatically partitioned" on a mesh.
# ---------------------------------------------------------------------------

def _v5e_mesh(monkeypatch, shape, names):
    from jax.experimental import topologies
    from mxnet_tpu.ops.pallas import attention
    # compile for the topology, not for the (CPU) default backend
    monkeypatch.setattr(attention, "_interpret", lambda: False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    n = int(onp.prod(shape))
    return jax.sharding.Mesh(
        onp.array(topo.devices[:n]).reshape(shape), names)


def _grad_hlo(fn, mesh, spec, shape, dtype):
    """Compiled HLO of fwd+bwd of ``fn(q, k, v)`` for operands of
    ``shape`` sharded by ``spec`` over ``mesh``."""
    x = jax.ShapeDtypeStruct(
        shape, dtype, sharding=jax.sharding.NamedSharding(mesh, spec))

    # a fresh closure per call: jax's trace cache must not replay a
    # trace made under another kernel_mesh context
    def loss(q, k, v):
        return fn(q, k, v).astype(jnp.float32).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))) \
        .lower(x, x, x).compile().as_text()


@pytest.mark.slow
@pytest.mark.parametrize("shape,dtype,causal,block_q", [
    ((8, 1024, 12, 64), jnp.bfloat16, True, 256),    # GPT-2 b8x1024
    ((48, 512, 12, 64), jnp.bfloat16, False, 512),   # BERT b48x512
    ((2, 2048, 12, 64), jnp.float32, True, 256),     # two-pass backward
])
def test_flash_aot_compiles_for_v5e_one_device(monkeypatch, shape, dtype,
                                               causal, block_q):
    mesh = _v5e_mesh(monkeypatch, (1,), ("dp",))
    P = jax.sharding.PartitionSpec
    hlo = _grad_hlo(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        block_q=block_q),
        mesh, P(), shape, dtype)
    assert "tpu_custom_call" in hlo


@pytest.mark.slow
def test_flash_aot_compiles_shard_mapped_over_v5e_2x2(monkeypatch):
    """q/k/v sharded over dp=2 x tp=2: bare, the kernel cannot lower
    (GSPMD cannot partition a Mosaic call); under kernel_mesh the op
    layer shard_maps it — with bias and dropout-seed specs."""
    from mxnet_tpu.ops.transformer import _flash
    from mxnet_tpu.parallel.mesh import kernel_mesh
    mesh = _v5e_mesh(monkeypatch, (2, 2), ("dp", "tp"))
    P = jax.sharding.PartitionSpec
    spec, shape = P("dp", None, "tp", None), (8, 1024, 12, 64)
    kw = dict(scale=None, causal=True, block_q=256, block_k=1024,
              bias_grad=False)

    with pytest.raises(NotImplementedError, match="shard_map"):
        _grad_hlo(lambda q, k, v: _flash(q, k, v, None, None,
                                         dropout=0.0, **kw),
                  mesh, spec, shape, jnp.bfloat16)
    bias = jnp.zeros((8, 1, 1, 1024), jnp.bfloat16)     # key padding
    seed = jnp.asarray([3, 7], jnp.int32)
    with kernel_mesh(mesh, ("dp",)):
        for b, s, rate in ((None, None, 0.0), (bias, seed, 0.1)):
            hlo = _grad_hlo(lambda q, k, v: _flash(q, k, v, b, s,
                                                   dropout=rate, **kw),
                            mesh, spec, shape, jnp.bfloat16)
            assert "tpu_custom_call" in hlo


def test_flash_shard_mapped_matches_dense():
    """The kernel_mesh shard_map wrapper (batch over dp, heads over tp)
    computes what the dense reference does, values and gradients."""
    from mxnet_tpu.ops.transformer import _flash
    from mxnet_tpu.parallel.mesh import kernel_mesh, make_mesh
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    rng = onp.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(2, 16, 2, 8).astype("float32"))
               for _ in range(3))
    bias = jnp.asarray(rng.randn(2, 1, 1, 16).astype("float32"))

    def sharded(q, k, v):
        return _flash(q, k, v, bias, None, dropout=0.0, scale=None,
                      causal=True, block_q=16, block_k=16, bias_grad=False)

    def dense(q, k, v):
        t = lambda a: jnp.swapaxes(a, 1, 2)     # (B,T,H,D) <-> (B,H,T,D)
        return t(_dense_reference(t(q), t(k), t(v), 8 ** -0.5, True, bias))

    def vg(f):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: (f(q, k, v) ** 2).sum(), argnums=(0, 1, 2)))

    ref, gref = vg(dense)(q, k, v)
    with kernel_mesh(mesh, ("dp",)):
        out, g = vg(sharded)(q, k, v)
    assert g[0].sharding.spec == jax.sharding.PartitionSpec(
        "dp", None, "tp")
    onp.testing.assert_allclose(float(out), float(ref), rtol=1e-5)
    for a, b in zip(g, gref):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# the ragged decode-attention kernel (ops/pallas/decode_attention.py) under
# the same described v5e; its mathematics is tests/test_decode_attention.py
# ---------------------------------------------------------------------------

@pytest.fixture
def one_v5e(monkeypatch):
    try:
        mesh = _v5e_mesh(monkeypatch, (1,), ("dp",))
    except Exception as e:      # noqa: BLE001 - whatever stops the describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())


@pytest.mark.parametrize("shape,dtype,causal,block_q,grad,calls", [
    ((16, 512, 16, 64), jnp.bfloat16, False, 512, True, 2),    # bert_large
    ((1, 1024, 20, 64), jnp.float32, True, 256, False, 1),     # gpt2_774m
    ((1, 1024, 16, 128), jnp.bfloat16, True, 256, False, 1),   # ouro_2_6b
    ((1, 1024, 128, 128), jnp.bfloat16, False, 256, False, 1),  # command_a+
])
def test_flash_compiles_for_v5e_with_no_copy_of_a_projection(
        one_v5e, shape, dtype, causal, block_q, grad, calls):
    """At the cells' shapes, fed from (B, T, H·D) with the key-padding
    bias: Mosaic takes the kernels (one forward, one fused backward),
    and the compiled program neither copies nor transposes an array the
    size of a projection on their way in or out (the parent's did 12
    times a layer: CHANGES.md, PR 36)."""
    import chip_smoke
    B, T, H, D = shape
    bias = jnp.zeros((B, 1, 1, T), dtype)

    def fn(q, k, v):
        split = lambda a: a.reshape(B, T, H, D)         # noqa: E731
        return flash_attention(split(q), split(k), split(v), causal=causal,
                               block_q=block_q, bias=bias, bias_grad=False
                               ).reshape(B, T, H * D)

    if grad:
        hlo = _grad_hlo(fn, one_v5e.mesh, one_v5e.spec, (B, T, H * D),
                        dtype)
    else:
        x = jax.ShapeDtypeStruct((B, T, H * D), dtype, sharding=one_v5e)
        hlo = jax.jit(fn).lower(x, x, x).compile().as_text()
    assert len(_kernel_calls(hlo)) == calls
    assert chip_smoke.cache_sized_relayouts(hlo, B * T * H * D) == []


def _fusions_holding(hlo, op):
    """``{fusion name: [the op_name metadata of its convolutions]}`` for
    every fusion of an optimized HLO module whose fused computation (or
    one it calls) holds an instruction that jax lowered from ``op``
    (its metadata's op_name ends in ``/<op>``)."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)

    def lines(name, seen=()):
        for line in comps.get(name, []):
            yield line
            for sub in re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", line):
                if sub not in seen:
                    yield from lines(sub, seen + (name,))

    found = {}
    for body in comps.values():
        for line in body:
            m = re.match(r"\s*%([\w.\-]+) = .* fusion\(.*calls=%([\w.\-]+)",
                         line)
            if not m:
                continue
            inner = list(lines(m.group(2)))
            if any(re.search(rf'op_name="[^"]*/{op}"', s) for s in inner):
                found[m.group(1)] = [
                    re.search(r'op_name="([^"]*)"', s).group(1)
                    for s in inner if " convolution(" in s]
    return found


def test_bert_layer_step_compiles_for_v5e_with_one_gelu_evaluation(
        one_v5e, monkeypatch):
    """The zoo's BERT layer (post-LN, exact GELU, bfloat16) forward and
    backward at bert_large.train_mlm512's shapes: the erfc of the exact
    GELU is evaluated in ONE fusion, FFN1's forward, which also forms
    its derivative; FFN2's forward, FFN2's backward to its input and
    dW2 read what it stored.  The parent's step held the erfc in six
    fusions of the layer: those three matmul fusions (each then bound by
    the vector unit, 41-45 % of its matmul's peak on the chip), FFN1's
    forward (for a sign mask) and two elementwise fusions."""
    from mxnet_tpu import _tape
    from mxnet_tpu.gluon.block import _bind_params
    from mxnet_tpu.gluon.model_zoo.bert import BERTEncoderLayer
    from mxnet_tpu.ndarray.ndarray import from_jax
    monkeypatch.setenv("MXNET_ATTENTION_USE_PALLAS", "1")
    B, T, C, F, H = 16, 512, 1024, 4096, 16
    layer = BERTEncoderLayer(C, F, H, dropout=0.0)
    layer.initialize()
    layer.cast("bfloat16")
    params = list(layer.collect_params().values())
    arg = lambda shape: jax.ShapeDtypeStruct(      # noqa: E731
        shape, jnp.bfloat16, sharding=one_v5e)

    def loss(pa, x):
        with _bind_params(params, pa):
            prev = _tape.set_training(True)
            try:
                y = layer.forward(from_jax(x),
                                  from_jax(jnp.ones((B, 1, 1, T), bool)))
            finally:
                _tape.set_training(prev)
        return y._data.astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        [arg(p.shape) for p in params], arg((B, T, C))).compile().as_text()
    erfc = _fusions_holding(hlo, "erfc")
    assert len(erfc) == 1, sorted(erfc)
    (convs,) = erfc.values()
    assert len(convs) == 1 and "ffn/up" in convs[0] \
        and "transpose" not in convs[0], convs


def test_decode_attention_compiles_for_v5e_at_the_serving_cells_shapes(
        one_v5e):
    """64 slots, 1280 K/V channels, the 4096 bucket, bfloat16, the
    module's own block: Mosaic takes the kernel within the scoped VMEM,
    and the rows reach it as they are (no copy of a whole buffer)."""
    import chip_smoke
    from mxnet_tpu.ops.pallas import decode_attention as da
    S, kv, L, d = 64, 1280, 4096, 64
    arg = lambda shape, dt: jax.ShapeDtypeStruct(      # noqa: E731
        shape, dt, sharding=one_v5e)

    def call(q, ck, cv, pos):
        return da.paired_decode_attention(q, ck, cv, pos, d)

    hlo = jax.jit(call).lower(
        arg((S, 2 * kv), jnp.bfloat16), arg((S, kv, L), jnp.bfloat16),
        arg((S, kv, L), jnp.bfloat16), arg((S,), jnp.int32)
    ).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert chip_smoke.cache_sized_relayouts(hlo, S * kv * L) == []


def _kernel_calls(hlo):
    """The names of an optimized HLO module's Mosaic calls, their
    numbering cut off: ``["write_columns", ..., "_step", ...]``."""
    return [re.sub(r"\.\d+$", "", m.group(1)) for m in re.finditer(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)]


def _column_updates(hlo, S, C):
    """The ``dynamic-update-slice`` instructions of an optimized HLO
    module (fused computations included) whose result is an
    ``(S, C, L)`` buffer: what the per-slot write of a ``(1, C, 1)``
    column compiled to (1152 of them in the hybrid step before the
    column-write kernel)."""
    result = re.compile(rf"= \w+\[{S},{C},\d+\][^ ]* dynamic-update-slice\(")
    return [line.strip()[:160] for line in hlo.splitlines()
            if result.search(line)]


@pytest.mark.parametrize("S,C,L", [(64, 1280, 512), (64, 1280, 4096),
                                   (48, 1024, 4096)])
def test_column_write_compiles_for_v5e_in_place_at_the_cells_shapes(
        one_v5e, S, C, L):
    """A layer's K and V through one call, bfloat16, the hybrid cell's
    ring and rows and the sparse-expert cell's: Mosaic takes the kernel
    (the word view of a tile, the 32-bit transpose of a slot's row),
    the donated buffers ARE the results (aliased whole, nothing held
    beside them) and nothing the size of one is copied or relaid."""
    import chip_smoke
    from mxnet_tpu.ops.pallas import column_write as cw
    arg = lambda shape, dt: jax.ShapeDtypeStruct(      # noqa: E731
        shape, dt, sharding=one_v5e)

    def call(kb, vb, kc, vc, at):
        return cw.write_columns((kb, vb), (kc[:, :, None], vc[:, :, None]),
                                at)

    compiled = jax.jit(call, donate_argnums=(0, 1)).lower(
        arg((S, C, L), jnp.bfloat16), arg((S, C, L), jnp.bfloat16),
        arg((S, C), jnp.bfloat16), arg((S, C), jnp.bfloat16),
        arg((S,), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert _kernel_calls(hlo) == ["write_columns"]
    assert chip_smoke.cache_sized_relayouts(hlo, S * C * L) == []
    assert _column_updates(hlo, S, C) == []
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 2 * S * C * L * 2
    assert memory.temp_size_in_bytes < 2 ** 20


def test_delta_step_compiles_for_v5e_in_place_at_the_cells_shape(one_v5e):
    """The delta-rule state kernel at the Olmo Hybrid cell's shape (40
    slots, 30 heads of 96 x 192, float32): Mosaic takes it (a head's k
    and q selected down the sublanes into the lanes it owns, 384-lane
    units), the donated state IS the result, and nothing the size of
    the state is copied on the way in or out."""
    import chip_smoke
    from mxnet_tpu.ops.pallas import delta_step as ds
    S, H, dk, dv = 40, 30, 96, 192
    arg = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_v5e)
    compiled = jax.jit(ds.delta_step, donate_argnums=(0,)).lower(
        arg((S, dk, H * dv)), arg((S, H, dk)), arg((S, H, dk)),
        arg((S, H, dv)), arg((S, H)), arg((S, H))).compile()
    hlo = compiled.as_text()
    assert _kernel_calls(hlo) == ["delta_step"]
    assert chip_smoke.cache_sized_relayouts(hlo, S * dk * H * dv) == []
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == S * dk * H * dv * 4
    assert memory.temp_size_in_bytes < 2 ** 22


@pytest.mark.slow
def test_hybrid_step_compiles_for_v5e_and_reads_the_rows_in_place(one_v5e):
    """The whole decode step of the published Phi-4-mini-flash, 64
    slots on the 4096 bucket (about a minute): eight ragged reads and
    nine column writes (a K/V pair a call: 8 rings, layer 17's rows),
    no per-slot column update left, and no copy or transpose the size
    of a ring or of layer 17's K or V rows on the way into or out of
    them (``chip_smoke.cache_sized_relayouts``, PR 27's check)."""
    import chip_smoke
    hlo, S, kv, L, cfg = _hybrid_step(one_v5e)
    calls = _kernel_calls(hlo)
    assert calls.count("write_columns") == 9 and len(calls) == 8 + 9
    assert _column_updates(hlo, S, kv) == []
    assert chip_smoke.cache_sized_relayouts(hlo, S * kv * L) == []
    assert chip_smoke.cache_sized_relayouts(
        hlo, S * kv * cfg["window"]) == []


# whole programs compiled for the described v5e, kept for the module:
# --dist loadfile keeps this file on one worker, so a minute-long
# compile runs once whichever tests read it
_COMPILED = {}


def _hybrid_step(one_v5e):
    """(optimized HLO, S, kv, L, cfg) of the hybrid family's decode step
    at the cell's shapes."""
    if "hybrid" in _COMPILED:
        return _COMPILED["hybrid"]
    from mxnet_tpu.gluon.model_zoo import phi4flash as pf
    from mxnet_tpu.serving.hybrid import CACHE_KIND, HybridDecodeModel
    S, L = 64, 4096
    arg = lambda shape, dt: jax.ShapeDtypeStruct(      # noqa: E731
        tuple(shape), dt, sharding=one_v5e)
    net = pf.get_phi4flash("phi4_mini_flash", dtype="bfloat16")
    cfg = dict(net.config)
    params = pf._tree({name: arg(p.shape, jnp.dtype(str(p.dtype)))
                       for name, p in net.collect_params().items()},
                      cfg["kinds"])
    model = HybridDecodeModel(params, cfg, net._max_length, "aot")
    kv = cfg["num_kv_heads"] * cfg["head_dim"]
    kinds = [CACHE_KIND[k] for k in cfg["kinds"]]
    rows = [arg((S, kv, L), jnp.bfloat16)] * kinds.count("rows")
    ring = [arg((S, kv, cfg["window"]), jnp.bfloat16)] \
        * kinds.count("window")
    n_state = kinds.count("state")
    state = {"wk": ring, "wv": ring,
             "conv": [arg((S, cfg["d_inner"], cfg["d_conv"] - 1),
                          jnp.float32)] * n_state,
             "ssm": [arg((S, cfg["d_inner"], cfg["d_state"]),
                         jnp.float32)] * n_state}
    i32, f32 = arg((S,), jnp.int32), arg((S,), jnp.float32)
    hlo = model._step_fn.lower(params, rows, rows, state, i32, i32, i32,
                               i32, f32, i32, f32, i32).compile().as_text()
    _COMPILED["hybrid"] = (hlo, S, kv, L, cfg)
    return _COMPILED["hybrid"]


# ---------------------------------------------------------------------------
# the Command A+ family (serving/moe.py) under the same described v5e: the
# ragged kernel at its packing, and its whole decode step
# ---------------------------------------------------------------------------

def test_decode_attention_compiles_for_v5e_at_the_moe_cells_shapes(one_v5e):
    """48 slots, 8 K/V heads of 128 as the kernel's groups with their 16
    query heads as rows, a 4096-wide ring or bucket, bfloat16: Mosaic
    takes the kernel, and the rows reach it as they are."""
    import chip_smoke
    from mxnet_tpu.ops.pallas import decode_attention as da
    S, G, R, C, L = 48, 8, 16, 128, 4096
    arg = lambda shape, dt: jax.ShapeDtypeStruct(      # noqa: E731
        shape, dt, sharding=one_v5e)

    def call(q, ck, cv, pos):
        return da.ragged_attention(q, ck, cv, pos, 1.0 / C ** 0.5)

    hlo = jax.jit(call).lower(
        arg((S, G, R, C), jnp.bfloat16), arg((S, G * C, L), jnp.bfloat16),
        arg((S, G * C, L), jnp.bfloat16), arg((S,), jnp.int32)
    ).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert chip_smoke.cache_sized_relayouts(hlo, S * G * C * L) == []


def _described_moe_model(one_v5e):
    from mxnet_tpu.gluon.model_zoo import cohere2moe as c2
    from mxnet_tpu.serving.moe import MoEDecodeModel
    arg = lambda shape, dt: jax.ShapeDtypeStruct(      # noqa: E731
        tuple(shape), dt, sharding=one_v5e)
    net = c2.get_cohere2moe("command_a_plus_ep8", dtype="bfloat16")
    cfg = dict(net.config)
    params = c2._tree({name: arg(p.shape, jnp.dtype(str(p.dtype)))
                       for name, p in net.collect_params().items()},
                      cfg["num_layers"])
    return MoEDecodeModel(params, cfg, net._max_length, "aot"), params, \
        cfg, arg


def _moe_step(one_v5e):
    """(compiled decode step, S, kv, L) of one chip's share of Command
    A+ at the cell's shapes."""
    if "moe" not in _COMPILED:
        model, params, cfg, arg = _described_moe_model(one_v5e)
        S, L, W = 48, 4096, cfg["window"]
        kv = cfg["num_kv_heads"] * cfg["head_dim"]
        rows = [arg((S, kv, L), jnp.bfloat16)]
        ring = [arg((S, kv, W), jnp.bfloat16)] * 3
        i32, f32 = arg((S,), jnp.int32), arg((S,), jnp.float32)
        toks = arg((S + model.n_layers * model.held,), jnp.int32)
        _COMPILED["moe"] = (model._step_fn.lower(
            params, rows, rows, {"wk": ring, "wv": ring}, toks, i32, i32,
            i32, f32, i32, f32, i32).compile(), S, kv, L)
    return _COMPILED["moe"]


@pytest.mark.slow
def test_moe_step_compiles_for_v5e_and_reads_every_cache_in_place(one_v5e):
    """The whole decode step of one chip's share of Command A+, 48
    slots on the 4096 bucket (about half a minute): four ragged reads
    and four column writes (three rings, one rows cache, a K/V pair a
    call), no per-slot column update left, no copy or transpose the
    size of a cache on the way into or out of them, and temporaries far
    under a cache."""
    import chip_smoke
    compiled, S, kv, L = _moe_step(one_v5e)
    hlo = compiled.as_text()
    calls = _kernel_calls(hlo)
    assert calls.count("write_columns") == 4 and len(calls) == 4 + 4
    assert _column_updates(hlo, S, kv) == []
    assert chip_smoke.cache_sized_relayouts(hlo, S * kv * L) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28


@pytest.mark.slow
def test_moe_prefill_compiles_for_v5e_with_flash_and_the_grouped_matmul(
        one_v5e, monkeypatch):
    """A 1024-token prefill: the flash kernel at head dim 128 over K/V
    heads repeated to the 128 query heads, and two megablox grouped
    matmuls a layer over the held experts' segments."""
    from mxnet_tpu.ops import transformer
    monkeypatch.setattr(transformer, "_use_pallas_len", lambda T: T >= 512)
    model, params, cfg, arg = _described_moe_model(one_v5e)
    compiled = model._prefill_fn.lower(
        params, arg((1024,), jnp.int32), arg((), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 4 * 3
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


# ---------------------------------------------------------------------------
# the looped (Ouro) family (serving/loop.py): ONE kernel call a pass on a
# STACKED cache, the entry scalar-prefetched, from inside a loop; the
# stacked forms of the two kernels it replaced are the pair it is held to
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("entry", [0, 1, 3])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 1e-6)])
def test_stacked_decode_attention_equals_the_plain_form_entry_by_entry(
        dtype, tol, entry):
    """Interpret mode: entry ``entry`` of the stacked rows through the
    leading-axis form is the plain form on that entry alone, the same
    arithmetic in the same order."""
    from mxnet_tpu.ops.pallas import decode_attention as da
    E, S, G, R, C, L = 4, 3, 2, 1, 16, 1024
    k = jax.random.split(jax.random.PRNGKey(entry), 3)
    q = jax.random.normal(k[0], (S, G, R, C)).astype(dtype)
    ck = jax.random.normal(k[1], (E, S, G * C, L)).astype(dtype)
    cv = jax.random.normal(k[2], (E, S, G * C, L)).astype(dtype)
    pos = jnp.asarray([0, 511, 700], jnp.int32)
    got = jax.jit(da.ragged_attention, static_argnums=4)(
        q, ck, cv, pos, 0.25, jnp.int32(entry))
    want = da.ragged_attention(q, ck[entry], cv[entry], pos, 0.25)
    assert got.shape == (S, G, R, C) and got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) <= tol
    with pytest.raises(ValueError, match="stacked"):
        da.ragged_attention(q, ck, cv, pos, 0.25)


def _loop_of_passes(E, S, G, C, L, form):
    """A looped family's passes, a ``fori_loop`` over the entries with
    the stacked K and V carried: ``form`` ``"one call"`` as its decode
    step makes them, ``"pair"`` the column write then the ragged read
    that the one call replaced (PR 35's step)."""
    from mxnet_tpu.ops.pallas import column_write as cw
    from mxnet_tpu.ops.pallas import decode_attention as da

    def step(K, V, x, pos):
        def body(e, carry):
            K, V, x = carry
            q = x.reshape(S, G, 1, C)
            if form == "pair":
                K, V = cw.write_columns((K, V), (x, x), pos, entry=e)
                a = da.ragged_attention(q, K, V, pos, C ** -0.5, entry=e)
            else:
                a, K, V = da.append_and_attend(q, K, V, x, x, pos,
                                               C ** -0.5, e)
            return K, V, a.reshape(S, G * C).astype(x.dtype)
        return jax.lax.fori_loop(0, E, body, (K, V, x))
    return step


@pytest.mark.parametrize("form,calls", [
    ("one call", ["ragged_attention"]),
    ("pair", ["ragged_attention", "write_columns"])])
def test_the_stacked_kernels_compile_for_v5e_in_a_loop_at_the_cells_shapes(
        one_v5e, form, calls):
    """192 entries, 5 slots, 16 heads of 128, the 1024 bucket, bfloat16
    (ouro_2_6b.serve_math): Mosaic takes the one call a pass (the rows
    left in HBM, its own copies of 128-position blocks, the tile copied
    back into the aliased stack) and the pair of leading-axis forms it
    replaced; the program holds ONE Mosaic call (one of each for the
    pair) inside one ``while``, the two stacks (8.05 GB) are aliased
    whole to the results, and nothing the size of an entry (or of the
    stack) is copied, sliced out or relaid."""
    import chip_smoke
    E, S, G, C, L = 192, 5, 16, 128, 1024
    arg = lambda shape, dt: jax.ShapeDtypeStruct(      # noqa: E731
        shape, dt, sharding=one_v5e)
    stack = arg((E, S, G * C, L), jnp.bfloat16)
    compiled = jax.jit(_loop_of_passes(E, S, G, C, L, form),
                       donate_argnums=(0, 1)).lower(
        stack, stack, arg((S, G * C), jnp.bfloat16),
        arg((S,), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert sorted(_kernel_calls(hlo)) == calls
    assert len(re.findall(r" while\(", hlo)) == 1
    for size in (S * G * C * L, E * S * G * C * L):
        assert chip_smoke.cache_sized_relayouts(hlo, size) == []
    assert not re.search(
        rf"= bf16\[{S},{G * C},{L}\][^ ]* (dynamic-slice|copy)\(", hlo)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * E * S * G * C * L * 2
    assert memory.temp_size_in_bytes < 2 ** 24


def _described_loop_model(one_v5e):
    from mxnet_tpu.gluon.model_zoo import ouro
    from mxnet_tpu.serving.loop import LoopDecodeModel
    arg = lambda shape, dt: jax.ShapeDtypeStruct(      # noqa: E731
        tuple(shape), dt, sharding=one_v5e)
    net = ouro.get_ouro("ouro_2_6b", dtype="bfloat16")
    params = ouro._tree({name: arg(p.shape, jnp.dtype(str(p.dtype)))
                         for name, p in net.collect_params().items()})
    return LoopDecodeModel(params, dict(net.config), net._max_length,
                           "aot"), params, arg


def _loop_step(one_v5e):
    """(compiled decode step, S, L, E, C) of the published Ouro-2.6B at
    the cell's shapes."""
    if "loop" not in _COMPILED:
        model, params, arg = _described_loop_model(one_v5e)
        S, L, E, C = 5, 1024, 192, 2048
        rows = [arg((E, S, C, L), jnp.bfloat16)]
        i32, f32 = arg((S,), jnp.int32), arg((S,), jnp.float32)
        _COMPILED["loop"] = (model._step_fn.lower(
            params, rows, rows, i32, i32, i32, i32, f32, i32, f32,
            i32).compile(), S, L, E, C)
    return _COMPILED["loop"]


@pytest.mark.slow
def test_loop_step_compiles_for_v5e_as_a_loop_with_the_cache_in_place(
        one_v5e):
    """The whole decode step of the published Ouro-2.6B, 5 slots on the
    1024 bucket (about half a minute): ONE Mosaic call (the read that
    writes the column; no ``write_columns`` beside it) and two ``while``
    loops for 192 layer passes, the stacked K and V aliased to the
    results, no copy of an entry or of a layer's weights (their
    ``dynamic-slice`` is fused into the product that reads them), and
    temporaries of a few MB."""
    import chip_smoke
    compiled, S, L, E, C = _loop_step(one_v5e)
    hlo = compiled.as_text()
    assert _kernel_calls(hlo) == ["ragged_attention"]
    assert len(re.findall(r" while\(", hlo)) == 2
    for size in (S * C * L, E * S * C * L):
        assert chip_smoke.cache_sized_relayouts(hlo, size) == []
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * E * S * C * L * 2
    assert memory.temp_size_in_bytes < 2 ** 26


@pytest.mark.slow
def test_loop_prefill_compiles_for_v5e_as_a_loop_with_flash_at_1024(
        one_v5e, monkeypatch):
    from mxnet_tpu.ops import transformer
    monkeypatch.setattr(transformer, "_use_pallas_len", lambda T: T >= 512)
    model, params, arg = _described_loop_model(one_v5e)
    compiled = model._prefill_fn.lower(
        params, arg((1024,), jnp.int32), arg((), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 1
    assert len(re.findall(r" while\(", hlo)) == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


# ---------------------------------------------------------------------------
# from instruction to component (tracing.hlo_scopes) on the optimized v5e
# text of the programs above: what the per-layer readers and
# profiler.device_summary rest on
# ---------------------------------------------------------------------------

def _matmul_fusions(hlo):
    """The ENTRY-level and loop-body ``fusion`` instructions of an
    optimized HLO module whose fused computation holds a ``dot`` or a
    ``convolution``, found without tracing.hlo_scopes: by the text."""
    holds, fused, found, name = set(), set(), [], None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if m:
            name = m.group(1)
            continue
        if re.search(r" (dot|convolution)\(", line):
            holds.add(name)
        m = re.match(r"^\s+(?:ROOT )?%([\w.\-]+) = .* fusion\(.*"
                     r"calls=%([\w.\-]+)", line)
        if m:
            fused.add(m.group(2))
            found.append((name, m.group(1), m.group(2)))
    # a fusion inside a fused computation is no leaf
    return [n for comp, n, called in found
            if called in holds and comp not in fused]


def _flash_step_hlo(one_v5e, monkeypatch):
    """BERT-large's attention forward + backward through the op the zoo
    calls (``npx.multi_head_attention``), b16 x 512, 16 heads of 64."""
    if "flash" not in _COMPILED:
        from mxnet_tpu.ndarray.ndarray import from_jax
        from mxnet_tpu.ops import transformer
        monkeypatch.setattr(transformer, "_use_pallas_len",
                            lambda T: T >= 512)

        def fn(q, k, v):
            return transformer.multi_head_attention(
                from_jax(q), from_jax(k), from_jax(v), 16)._data

        _COMPILED["flash"] = _grad_hlo(fn, one_v5e.mesh, one_v5e.spec,
                                       (16, 512, 1024), jnp.bfloat16)
    return _COMPILED["flash"]


@pytest.mark.parametrize("program", ["flash", "hybrid", "moe", "loop"])
def test_every_matmul_and_kernel_of_a_v5e_program_resolves_to_a_component(
        one_v5e, monkeypatch, program):
    """On the optimized text of the hybrid, sparse-expert and looped
    families' whole decode steps and of BERT's attention forward +
    backward: every leaf fusion that holds a dot or a convolution and
    every Mosaic call has a component; the ragged read is attn/core, the
    column write cache/write, and the flash backward kernel bwd."""
    from mxnet_tpu import tracing
    hlo = {"flash": lambda: _flash_step_hlo(one_v5e, monkeypatch),
           "hybrid": lambda: _hybrid_step(one_v5e)[0],
           "moe": lambda: _moe_step(one_v5e)[0].as_text(),
           "loop": lambda: _loop_step(one_v5e)[0].as_text()}[program]()
    scopes, _ = tracing.hlo_scopes(hlo)
    fusions = _matmul_fusions(hlo)
    assert fusions or program == "flash"
    unresolved = [n for n in fusions
                  if scopes.get(n, ("unscoped",))[0] == "unscoped"]
    assert unresolved == [], unresolved[:5]
    kernels = [m.group(1) for m in re.finditer(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        hlo)]
    assert kernels and all(k in scopes for k in kernels)
    by_kind = {}
    for k in kernels:
        by_kind.setdefault(re.sub(r"\.\d+$", "", k), set()).add(scopes[k])
    if program == "flash":
        assert sorted(by_kind.values(), key=str) == [
            {("attn", "core", "bwd")}, {("attn", "core", "fwd")}]
        return
    assert by_kind["ragged_attention"] == {("attn", "core", "fwd")}
    # the looped family's read writes the column itself
    assert by_kind.get("write_columns") == (
        None if program == "loop" else {("cache", "write", "fwd")})
    # the loops are around their children, not leaves beside them
    assert not any(re.match(r"while(\.\d+)?$", n) for n in scopes)
    comps = {c for c, _, _ in scopes.values()}
    assert {"attn", "head", "sample", "embed"} <= comps
    assert "cache" in comps or program == "loop"
    assert {"hybrid": {"ssm", "ffn", "norm"}, "moe": {"experts", "norm"},
            "loop": {"ffn", "norm"}}[program] <= comps
