"""Dump recorded spans as a Chrome/Perfetto trace-event JSON file.

Two sources:

* ``--url http://host:port`` — fetch ``GET /v1/traces`` from a running
  ModelServer/GenerationServer HTTP endpoint (the span ring of that
  process, already in trace-event shape).
* ``--demo`` — run a small fully-sampled generation workload in THIS
  process and dump its span ring (no server needed; a smoke of the
  whole tracing path).

The output is the same ``traceEvents`` format ``mxnet_tpu.profiler``
dumps, so one ``chrome://tracing`` / https://ui.perfetto.dev load shows
spans and profiler op timings side by side.  Span events carry their
``trace_id``/``span_id``/``parent_id`` (and any links) in ``args`` —
Perfetto's query/search finds every span of one request by trace id.

``--device LOGDIR`` (with ``--demo``) also runs the workload under
``mx.profiler.start_xla_trace(LOGDIR)`` and prints
``mx.profiler.device_summary(LOGDIR)`` on stderr: device seconds by
program role and by component of the model.  It needs the process that
compiled the programs (their optimized HLO says which component an
instruction belongs to), so it cannot go with ``--url``; on the CPU
backend the trace holds no device plane and the summary is empty.

    python tools/trace_dump.py --url http://127.0.0.1:8080 --out t.json
    python tools/trace_dump.py --demo --out demo-trace.json
    python tools/trace_dump.py --demo --platform ambient --device /tmp/xla
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fetch(url: str, timeout: float) -> dict:
    import urllib.request
    req = urllib.request.Request(url.rstrip("/") + "/v1/traces")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode("utf-8"))


def _demo(device_logdir=None) -> dict:
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import profiler, tracing
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    from mxnet_tpu.serving import (DecodeModel, GenerationEngine,
                                   GenerationServer)

    tracing.configure(sample=1.0)
    mx.random.seed(0)
    gpt = GPTModel(vocab_size=97, num_layers=2, units=32,
                   hidden_size=48, num_heads=4, max_length=64,
                   dropout=0.0)
    gpt.initialize(mx.init.Normal(1.0))
    gpt(mx.np.zeros((1, 4), dtype="int32"))
    eng = GenerationEngine(DecodeModel.from_block(gpt), max_slots=2,
                           kv_buckets=(16, 32), max_tokens=16)
    eng.warmup()
    rng = onp.random.RandomState(0)
    if device_logdir:
        profiler.start_xla_trace(device_logdir)
    with GenerationServer(eng) as gs:
        for i in range(3):
            with tracing.span("client.request", i=i):
                gs.generate(rng.randint(1, 90, (4,)).astype("int32"),
                            max_new_tokens=6).result(timeout=60)
    mx.waitall()
    if device_logdir:
        profiler.stop_xla_trace()
        sys.stderr.write(json.dumps(
            profiler.device_summary(device_logdir), indent=1) + "\n")
    return tracing.export_trace_events()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--url",
                     help="server base URL; fetches GET /v1/traces")
    src.add_argument("--demo", action="store_true",
                     help="run a local traced generation workload and "
                          "dump this process's span ring")
    ap.add_argument("--device", metavar="LOGDIR",
                    help="with --demo: trace the device into LOGDIR and "
                         "print profiler.device_summary(LOGDIR) on stderr")
    ap.add_argument("--out", default="-",
                    help="output file ('-' = stdout, the default)")
    ap.add_argument("--timeout", type=float, default=10.0,
                    help="HTTP timeout for --url (seconds)")
    ap.add_argument("--platform", choices=("cpu", "ambient"),
                    default="cpu",
                    help="--demo backend: force CPU (default) or keep "
                         "the environment's")
    args = ap.parse_args(argv)
    if args.device and not args.demo:
        ap.error("--device reads the optimized HLO of the process that "
                 "compiled the programs: it goes with --demo")

    if args.demo and args.platform == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")

    payload = _fetch(args.url, args.timeout) if args.url \
        else _demo(args.device)
    n = sum(1 for e in payload.get("traceEvents", ())
            if e.get("ph") == "X")
    text = json.dumps(payload, indent=1)
    if args.out == "-":
        print(text)
    else:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.out}: {n} span events "
              "(load in chrome://tracing or ui.perfetto.dev)")


if __name__ == "__main__":
    main()
