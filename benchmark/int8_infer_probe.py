"""INT8 post-training-quantized inference vs bf16 on the real chip.

Measures ResNet-50 b128 forward throughput for (a) the bf16 model and
(b) the same model through ``contrib.quantization.quantize_net`` (naive
calibration, one batch) — evidence for whether the v5e's int8 MXU rate
(2x bf16 nominal) survives the quantize/dequantize traffic XLA emits
around each int8 dot at inference batch sizes.

Timing: the host fetch of a scalar is the sync point; whole-batch jit
amortizes the dispatch floor.

Usage: python benchmark/int8_infer_probe.py [batch]
"""
import sys
import time

import numpy as onp

import os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import mxnet_tpu as mx  # noqa: E402


def timed(net, x, n=30):
    net(x).asnumpy()
    net(x).asnumpy()
    t0 = time.perf_counter()
    for _ in range(n):
        y = net(x)
    y.asnumpy()
    return (time.perf_counter() - t0) / n


def main():
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    rng = onp.random.RandomState(0)
    x_np = rng.uniform(-1, 1, (B, 3, 224, 224)).astype("float32")

    mx.random.seed(0)
    net = mx.gluon.model_zoo.vision.resnet50_v1()
    net.initialize()
    net(mx.np.zeros((1, 3, 64, 64)))      # settle shapes

    # bf16 arm
    net.cast("bfloat16")
    net.hybridize()
    x16 = mx.np.array(x_np.astype("bfloat16"))
    t_bf16 = timed(net, x16)
    print(f"bf16  fwd: {t_bf16 * 1e3:7.2f} ms/batch "
          f"({B / t_bf16:8.1f} img/s)", flush=True)

    # int8 arm: fresh float net, calibrate on one small batch, quantize
    mx.random.seed(0)
    qnet = mx.gluon.model_zoo.vision.resnet50_v1()
    qnet.initialize()
    qnet(mx.np.zeros((1, 3, 64, 64)))
    from mxnet_tpu.contrib.quantization import quantize_net
    calib = [(mx.np.array(x_np[:8]), None)]
    quantize_net(qnet, calib_data=calib, calib_mode="naive")
    qnet.hybridize()
    x32 = mx.np.array(x_np)
    t_int8 = timed(qnet, x32)
    print(f"int8  fwd: {t_int8 * 1e3:7.2f} ms/batch "
          f"({B / t_int8:8.1f} img/s)  ratio bf16/int8: "
          f"{t_bf16 / t_int8:4.2f}x", flush=True)


if __name__ == "__main__":
    main()
