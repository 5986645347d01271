"""Serve an exported model over HTTP — the production front door.

Loads an ``export()`` artifact (or a freshly-initialized zoo model, for
tire-kicking without a training run), wraps it in the serving
subsystem's dynamically-batched, shape-bucketed, load-shedding
``ModelServer`` (``mxnet_tpu/serving/``), pre-compiles every configured
bucket, and answers on a stdlib HTTP server:

    python tools/serve.py model                 # model-symbol.json + .params
    python tools/serve.py --zoo resnet18_v1 --input-shape 3,32,32
    python tools/serve.py model --port 8080 --max-batch 16 \
        --batch-timeout-ms 3 --queue-limit 512
    python tools/serve.py --generate --zoo-gpt gpt2_124m   # decoder LM:
        # continuous-batching /v1/generate with per-token streaming
    python tools/serve.py --generate --zoo-phi4flash phi4_mini_flash \
        --max-slots 64 --kv-buckets 1024,2048,4096    # hybrid LM, bfloat16
    python tools/serve.py --generate --zoo-cohere2moe command_a_plus_ep8 \
        --max-slots 48 --kv-buckets 1024,2048,4096    # sparse experts, bfloat16
    python tools/serve.py --generate --zoo-ouro ouro_2_6b \
        --max-slots 5 --kv-buckets 1024       # looped LM (48 layers x 4), bfloat16

    curl -s localhost:8080/v1/inference -d '{"instances": [[...]]}'
    curl -sN localhost:8080/v1/generate \
        -d '{"tokens": [464, 2068], "max_new_tokens": 32}'
    curl -s localhost:8080/metrics          # Prometheus text
    curl -s localhost:8080/healthz

Knobs default from the MXNET_SERVING_* env tier, plus MXNET_GEN_* for
--generate (docs/serving.md).  Static exports serve exactly their
traced batch size; export with ``dynamic_batch=True`` for the full
bucket grid.  --generate serves a LIVE decoder LM (zoo GPT in float32,
with --zoo-phi4flash the Phi-4-mini-flash hybrid, with
--zoo-cohere2moe one chip's share of Command A+ or with --zoo-ouro the
looped Ouro-2.6B in bfloat16, for all three of which speculation and
the prefix cache are refused; optionally with --gpt-params weights)
through the resident decode loop.

Resilience (docs/serving.md#resilience): --replicas N hosts N worker
replicas (dead workers requeue/recover their requests and restart with
backoff behind a circuit breaker), and SIGTERM/SIGINT triggers a
graceful drain — admissions shed 429, resident work finishes inside
MXNET_SERVING_DRAIN_DEADLINE_S, readiness 503 / liveness 200
throughout, exit 0.

Warm restarts (docs/performance.md#persistent-compile-cache): on an
accelerator jax's persistent compilation cache is on
(JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache), so a restarted
server loads every bucket grid from it BEFORE /healthz flips ready
(zero XLA compiles) and /v1/model reports warmup_seconds and what this
boot compiled and loaded.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("model", nargs="?",
                    help="export prefix (or the -symbol.json path)")
    ap.add_argument("--params", default=None,
                    help="explicit .params file (default: newest next to "
                         "the symbol json)")
    ap.add_argument("--zoo", default=None,
                    help="serve a freshly-initialized model_zoo model "
                         "instead of an export (smoke/demo)")
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--input-shape", default="3,32,32",
                    help="zoo sample shape WITHOUT batch (default "
                         "3,32,32)")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--batch-buckets", default=None,
                    help="comma list, e.g. 1,2,4,8 (default: powers of "
                         "two up to --max-batch)")
    ap.add_argument("--batch-timeout-ms", type=float, default=None)
    ap.add_argument("--queue-limit", type=int, default=None)
    ap.add_argument("--pad-axis", type=int, default=None,
                    help="sample axis for length bucketing (variable-"
                         "shape requests; model must tolerate padding)")
    ap.add_argument("--length-buckets", default=None,
                    help="comma list of padded lengths for --pad-axis")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip pre-compiling the bucket grid at startup")
    ap.add_argument("--prewarm", action="store_true",
                    help="populate the bucket grids BEFORE /healthz "
                         "flips ready (the default behavior, made "
                         "explicit for launch scripts) and print the "
                         "warmup report — a restarted server loads "
                         "its programs from jax's persistent cache "
                         "(JAX_COMPILATION_CACHE_DIR) with zero XLA "
                         "compiles; warmup seconds are also reported "
                         "in /v1/model")
    ap.add_argument("--replicas", type=int, default=None,
                    help="worker replicas (MXNET_SERVING_REPLICAS): a "
                         "dead worker's requests requeue/recover onto "
                         "the survivors while it restarts")
    ap.add_argument("--drain-deadline-s", type=float, default=None,
                    help="graceful-drain budget on SIGTERM/SIGINT "
                         "(MXNET_SERVING_DRAIN_DEADLINE_S)")
    ap.add_argument("--generate", action="store_true",
                    help="serve a decoder LM through the continuous-"
                         "batching generation engine (POST /v1/generate "
                         "with per-token streaming) instead of one-shot "
                         "inference")
    ap.add_argument("--zoo-gpt", default="gpt2_124m",
                    help="GPT zoo spec for --generate (default "
                         "gpt2_124m; 'tiny' builds a 2-layer demo LM "
                         "that boots in seconds on CPU; weights are "
                         "random unless --gpt-params is given)")
    ap.add_argument("--gpt-params", default=None,
                    help="a .params file to load into the --zoo-gpt, "
                         "--zoo-phi4flash, --zoo-cohere2moe or "
                         "--zoo-ouro model before serving")
    ap.add_argument("--zoo-phi4flash", default=None,
                    choices=("phi4_mini_flash", "tiny"),
                    help="serve the Phi-4-mini-flash hybrid family "
                         "(Mamba state, windowed and full differential "
                         "attention, Gated Memory Units) for --generate "
                         "instead of --zoo-gpt: phi4_mini_flash is the "
                         "published 3.85 B model in bfloat16, 'tiny' an "
                         "8-layer float32 one for the CPU.  Speculation "
                         "and the prefix cache are refused for it")
    ap.add_argument("--zoo-cohere2moe", default=None,
                    choices=("command_a_plus_ep8", "tiny"),
                    help="serve the Command A+ (cohere2_moe) family for "
                         "--generate instead of --zoo-gpt: a parallel "
                         "block with sigmoid top-8 routing over 128 "
                         "experts, 4 averaged shared experts, window "
                         "layers with RoPE and full layers without.  "
                         "command_a_plus_ep8 is ONE CHIP'S SHARE of the "
                         "published 218 B model where 8 chips share each "
                         "layer (16 of the 128 routed experts held, one "
                         "period of 4 layers, an eighth of the "
                         "vocabulary; 4.7 B in bfloat16), 'tiny' a "
                         "float32 one for the CPU.  Speculation and the "
                         "prefix cache are refused for it")
    ap.add_argument("--zoo-ouro", default=None,
                    choices=("ouro_2_6b", "tiny"),
                    help="serve the Ouro (LoopLM) family for --generate "
                         "instead of --zoo-gpt: ONE stack of sandwich-"
                         "norm layers (RMSNorm before and after each "
                         "branch, half-split RoPE, SwiGLU) applied "
                         "several times a token with the same weights, "
                         "a K/V cache entry for every (loop step, "
                         "layer).  ouro_2_6b is the published 2.67 B "
                         "model whole (48 layers x 4 loop steps; "
                         "1.5 MiB of cache a position, so 5 slots of "
                         "1024 on a 16 GB chip) in bfloat16, 'tiny' a "
                         "3-layer x 3-step float32 one for the CPU.  "
                         "Speculation and the prefix cache are refused "
                         "for it")
    ap.add_argument("--max-slots", type=int, default=None,
                    help="decode slots for --generate "
                         "(MXNET_GEN_MAX_SLOTS)")
    ap.add_argument("--kv-buckets", default=None,
                    help="comma list of KV capacity buckets for "
                         "--generate (MXNET_GEN_KV_BUCKETS)")
    ap.add_argument("--method", default=None,
                    choices=("greedy", "sample", "top_k", "top_p"),
                    help="default decode method for --generate "
                         "requests that name none (MXNET_GEN_METHOD); "
                         "sampling runs on-device, deterministic per "
                         "request seed")
    ap.add_argument("--temperature", type=float, default=None,
                    help="default sampling temperature for --generate "
                         "(MXNET_GEN_TEMPERATURE; > 0)")
    ap.add_argument("--top-k", type=int, default=None,
                    help="default k for top_k decoding "
                         "(MXNET_GEN_TOP_K; >= 1)")
    ap.add_argument("--top-p", type=float, default=None,
                    help="default nucleus mass for top_p decoding "
                         "(MXNET_GEN_TOP_P; in (0, 1])")
    ap.add_argument("--prefix-cache-slots", type=int, default=None,
                    help="resident shared-prefix KV entries for "
                         "--generate (MXNET_GEN_PREFIX_CACHE_SLOTS; "
                         "0 disables prefix caching)")
    ap.add_argument("--spec-mode", default=None,
                    choices=("off", "self", "draft"),
                    help="speculative decoding for --generate "
                         "(MXNET_GEN_SPEC_MODE): 'self' drafts with "
                         "the target's own bottom layers; output "
                         "stays byte-identical to 'off' at the same "
                         "seed ('draft' needs an in-process draft "
                         "model and is API-only here)")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="draft tokens proposed per slot per "
                         "iteration (MXNET_GEN_SPEC_K; >= 1)")
    ap.add_argument("--spec-draft-layers", type=int, default=None,
                    help="target layers the self-speculative draft "
                         "keeps (MXNET_GEN_SPEC_DRAFT_LAYERS; 0 = "
                         "half)")
    ap.add_argument("--platform", choices=("cpu", "ambient"),
                    default="ambient",
                    help="force the CPU backend, or keep the "
                         "environment's (default)")
    ap.add_argument("--verbose", action="store_true",
                    help="log every HTTP request")
    args = ap.parse_args(argv)
    if args.prewarm and args.no_warmup:
        ap.error("--prewarm and --no-warmup are contradictory")

    if args.platform == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")

    from mxnet_tpu import serving
    from mxnet_tpu.runtime import device_info

    dev = device_info()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)

    if args.generate:
        return _serve_generate(args, serving)

    if args.zoo:
        import mxnet_tpu as mx
        from mxnet_tpu.gluon.model_zoo import vision as zoo
        shape = tuple(int(s) for s in args.input_shape.split(","))
        net = zoo.get_model(args.zoo, classes=args.classes)
        net.initialize()
        net.hybridize()
        net(mx.np.zeros((1,) + shape, dtype="float32"))
        model = serving.load_served(net)
    elif args.model:
        model = serving.load_served(args.model, param_file=args.params)
    else:
        ap.error("pass an export prefix or --zoo NAME")

    kw = {}
    if args.batch_buckets:
        kw["batch_buckets"] = [int(b) for b in
                               args.batch_buckets.split(",")]
    elif args.max_batch:
        kw["max_batch"] = args.max_batch
    if args.length_buckets:
        kw["pad_axis"] = args.pad_axis if args.pad_axis is not None else 0
        kw["length_buckets"] = [int(b) for b in
                                args.length_buckets.split(",")]
    policy = model.default_policy(**kw)

    print(f"model: {model.name}  inputs: "
          f"{[list(s) for s, _ in model.input_signature]}  "
          f"batch buckets: {list(policy.batch_buckets)}"
          + (f"  length buckets: {list(policy.length_buckets)}"
             if policy.length_buckets else ""))
    server = serving.ModelServer(model, policy,
                                 timeout_ms=args.batch_timeout_ms,
                                 queue_limit=args.queue_limit,
                                 warmup=not args.no_warmup,
                                 replicas=args.replicas)
    if server.warmed:
        print(f"warmup: {server.warmed} bucket signatures ready in "
              f"{server.warmup_seconds:.2f}s" + _cache_note())
    server.start()
    httpd = serving.make_http_server(server, args.host, args.port,
                                     verbose=args.verbose)
    host, port = httpd.server_address[:2]
    print(f"serving on http://{host}:{port}  "
          f"(POST /v1/inference, GET /metrics, /healthz, /livez, "
          f"/v1/model; {server.replicas} worker replica(s))",
          flush=True)
    # SIGTERM/SIGINT drains: admissions shed 429, resident work
    # finishes inside the deadline, readiness 503 / liveness 200, then
    # a clean exit — the zero-downtime rolling-restart contract
    drained = serving.serve_until_preempted(
        httpd, server, deadline_s=args.drain_deadline_s)
    print(f"drain {'complete' if drained else 'deadline exceeded'}; "
          "bye", flush=True)
    sys.exit(0 if drained else 1)


def _cache_note() -> str:
    """One-line persistent-cache summary for the startup banner."""
    from mxnet_tpu.serving.server import _compile_cache_stats
    stats = _compile_cache_stats()
    return (f"  [compile cache: {stats['dir'] or 'off'}, "
            f"{stats['compiled']} compiled / {stats['loaded']} loaded "
            "this boot]")


def _serve_generate(args, serving) -> None:
    """--generate mode: host a zoo GPT behind the continuous-batching
    engine (resident decode loop, paged KV cache, token streaming)."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel, get_gpt
    from mxnet_tpu.gluon.model_zoo.cohere2moe import get_cohere2moe
    from mxnet_tpu.gluon.model_zoo.ouro import get_ouro
    from mxnet_tpu.gluon.model_zoo.phi4flash import get_phi4flash

    mx.random.seed(0)
    declared = args.zoo_phi4flash or args.zoo_cohere2moe or args.zoo_ouro
    if declared:
        # the families that declare every shape; bfloat16 as published
        # but for the CPU size
        get = get_phi4flash if args.zoo_phi4flash else \
            get_cohere2moe if args.zoo_cohere2moe else get_ouro
        net = get(declared,
                  dtype="float32" if declared == "tiny" else "bfloat16")
        # inference only: a gradient buffer beside each of billions of
        # bfloat16 weights would not fit one chip
        net.collect_params().setattr("grad_req", "null")
    elif args.zoo_gpt == "tiny":     # CPU tire-kicking: boots fast
        net = GPTModel(vocab_size=503, num_layers=2, units=64,
                       hidden_size=128, num_heads=4, max_length=256,
                       dropout=0.0)
    else:
        net = get_gpt(args.zoo_gpt, dropout=0.0)
    net.initialize()
    if not declared:                 # finishes the deferred shapes
        net(mx.np.zeros((1, 4), dtype="int32"))
    if args.gpt_params:
        net.load_parameters(args.gpt_params)
        print(f"loaded weights: {args.gpt_params}")
    else:
        print("NOTE: serving RANDOM weights (pass --gpt-params for a "
              "trained model)")

    model = serving.DecodeModel.from_block(net)
    kv = ([int(b) for b in args.kv_buckets.split(",")]
          if args.kv_buckets else None)
    # ONE shared prefix store across replicas (same device, same
    # DecodeModel): a prefix any replica prefilled is hot for all of
    # them, and a resurrected sequence lands on warm rows
    # (a family that cannot share prefixes gets none unless slots were
    # asked for, which the engine then refuses by name)
    prefix = None \
        if not (model.supports_rollback or args.prefix_cache_slots) \
        else serving.PrefixCache(args.prefix_cache_slots)

    def engine_factory():
        # one engine per worker replica; the shared DecodeModel means
        # replicas (and restarts) reuse the same compiled programs
        return serving.GenerationEngine(model, max_slots=args.max_slots,
                                        kv_buckets=kv,
                                        queue_limit=args.queue_limit,
                                        prefix_cache=prefix,
                                        default_method=args.method,
                                        default_temperature=args.temperature,
                                        default_top_k=args.top_k,
                                        default_top_p=args.top_p,
                                        spec_mode=args.spec_mode,
                                        spec_k=args.spec_k,
                                        spec_draft_layers=args.spec_draft_layers)

    gs = serving.GenerationServer(engine_factory=engine_factory,
                                  replicas=args.replicas,
                                  warmup=not args.no_warmup)
    engine = gs.engine
    if engine.warmed:
        print(f"warmup: {engine.warmed} programs ready in "
              f"{gs.warmup_seconds:.2f}s "
              f"(prefill buckets {list(engine.prompt_buckets)}, "
              f"KV buckets {list(engine.grid)}, "
              f"{engine.max_slots} slots x {gs.replicas} replica(s), "
              f"{engine.cache.prefix.slots} prefix-cache slots, "
              f"default method {engine.default_method}, "
              f"speculation {engine.spec_mode}"
              + (f" k={engine.spec_k}" if engine._draft is not None
                 else "") + ")"
              + _cache_note())
    gs.start()
    httpd = serving.make_http_server(None, args.host, args.port,
                                     verbose=args.verbose,
                                     generation_server=gs)
    host, port = httpd.server_address[:2]
    print(f"serving on http://{host}:{port}  (POST /v1/generate "
          "[streaming], GET /metrics, /healthz, /livez, /v1/model; "
          f"{gs.replicas} worker replica(s))", flush=True)
    drained = serving.serve_until_preempted(
        httpd, gs, deadline_s=args.drain_deadline_s)
    print(f"drain {'complete' if drained else 'deadline exceeded'}; "
          "bye", flush=True)
    sys.exit(0 if drained else 1)


if __name__ == "__main__":
    main()
