"""Break down where a ResNet-50 training step spends wall-clock.

Per step it prints dispatch time (trainer.step returns — includes host
prep and input device_put, no device sync) and total time including the
loss sync; plus a one-off param-list-build cost and a pure-jax
matmul/conv calibration of the chip.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp
import jax
import jax.numpy as jnp


def calibrate():
    """Measure raw chip throughput + dispatch latency."""
    x = jnp.zeros((8192, 8192), jnp.bfloat16)

    @jax.jit
    def mm(a):
        for _ in range(8):
            a = a @ a
        return a

    mm(x).block_until_ready()
    t0 = time.perf_counter()
    r = mm(x)
    _ = onp.asarray(r[0, 0])
    dt = time.perf_counter() - t0
    fl = 8 * 2 * 8192**3 / dt
    print(f"[cal] 8x 8192^3 bf16 matmul: {dt*1e3:.1f} ms -> {fl/1e12:.1f} TFLOP/s")

    @jax.jit
    def tiny(a):
        return a + 1.0

    s = jnp.zeros((), jnp.float32)
    tiny(s)
    for _ in range(3):
        t0 = time.perf_counter()
        r = tiny(s)
        d1 = time.perf_counter() - t0
        _ = float(r)
        d2 = time.perf_counter() - t0
        print(f"[cal] tiny dispatch {d1*1e3:.2f} ms, +sync {d2*1e3:.2f} ms")

    # conv calibration: 20x same conv
    from jax import lax
    img = jnp.zeros((128, 56, 56, 256), jnp.bfloat16)
    ker = jnp.zeros((3, 3, 256, 256), jnp.bfloat16)

    @jax.jit
    def convs(a, k):
        for _ in range(20):
            a = lax.conv_general_dilated(
                a, k, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return a

    convs(img, ker).block_until_ready()
    t0 = time.perf_counter()
    _ = onp.asarray(convs(img, ker)[0, 0, 0, 0])
    dt = time.perf_counter() - t0
    fl = 20 * 2 * 128 * 56 * 56 * 9 * 256 * 256 / dt
    print(f"[cal] 20x conv3x3 256ch b128: {dt*1e3:.1f} ms -> {fl/1e12:.1f} TFLOP/s")


def profile_resnet(batch=128, dtype="bfloat16", steps=5):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision as zoo
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh, DATA_PARALLEL_RULES

    mx.random.seed(0)
    net = zoo.get_model("resnet50_v1", classes=1000)
    net.initialize()
    net(mx.np.zeros((1, 3, 64, 64), dtype="float32"))
    if dtype != "float32":
        net.cast(dtype)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = SPMDTrainer(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer="sgd", optimizer_params={"learning_rate": 0.1,
                                           "momentum": 0.9},
        mesh=mesh, rules=DATA_PARALLEL_RULES)
    x = mx.np.array(onp.random.uniform(-1, 1, (batch, 3, 224, 224))
                    .astype(dtype))
    y = mx.np.array(onp.random.randint(0, 1000, (batch,)).astype("int32"))

    from mxnet_tpu import metrics

    def _compiles():
        return metrics.value("mxnet_compile_misses_total")

    c0 = _compiles()
    t0 = time.perf_counter()
    float(trainer.step(x, y).asnumpy())
    print(f"[rn50] warmup1 (compile): {time.perf_counter()-t0:.1f} s "
          f"({_compiles()-c0:.0f} XLA compiles)")
    c0 = _compiles()
    t0 = time.perf_counter()
    float(trainer.step(x, y).asnumpy())
    print(f"[rn50] warmup2 (relayout): {time.perf_counter()-t0:.1f} s "
          f"({_compiles()-c0:.0f} XLA compiles)")

    for i in range(steps):
        c0 = _compiles()
        t0 = time.perf_counter()
        loss = trainer.step(x, y)
        d1 = time.perf_counter() - t0
        loss.asnumpy()
        d2 = time.perf_counter() - t0
        rc = _compiles() - c0
        # a non-zero recompile count means this step's timing includes
        # a silent re-trace+compile — discard it from averages
        note = f", RECOMPILED x{rc:.0f} (timing skewed)" if rc else ""
        print(f"[rn50] step {i}: dispatch {d1*1e3:.1f} ms, "
              f"+sync {d2*1e3:.1f} ms{note}")

    # host-side cost: param list build only
    t0 = time.perf_counter()
    pa = [p.data()._data for p in trainer._params]
    print(f"[rn50] param list build: {(time.perf_counter()-t0)*1e3:.2f} ms "
          f"({len(pa)} params)")


if __name__ == "__main__":
    calibrate()
    profile_resnet()
