"""Job kind ``serve_delta``: a zoo LM whose slots hold the delta rule's
float32 matrix state beside K/V rows (the Olmo Hybrid family:
``gluon.model_zoo.olmo_hybrid``) behind the continuous-batching engine,
built as tools/serve.py builds it (``DecodeModel.from_block`` ->
``GenerationEngine`` -> ``GenerationServer``) and driven in-process by
open-loop traffic.  The load, the clients' view and the counters are
``serve_generate``'s, the sampler ``serve_state``'s, the server's
build ``serve_moe``'s and the direct drive of the decode program
``serve_loop``'s, all unchanged, so ``chipbench/sweep.py`` and
``chipbench/precision.py`` work on a cell of this kind as they are.

Cell file keys: as ``serve_state`` (its soak too): ``check`` holds
``prompt_lengths``
(prefill: logits and everything it installs), ``forced`` (the decode
program driven directly with every slot live: ``prompts`` the compared
slots' prompt lengths, ``copies`` slots of each, ``steps`` forced
tokens, ``min_decisive``), ``decode_prompt`` and ``new_tokens`` (greedy
decoding through the engine), ``batch_prompts`` (the range of the other
prompts' lengths in a full batch).
Configuration keys: ``zoo``, ``zoo_args``, ``zoo_kwargs`` (with the
serving dtype and the depth held), ``serve_dtype``, the ``arch`` group.
"""
import gc
import threading
import time

import numpy as np

from chipbench.harness import delta_bytes, trace_reduce, traffic
from chipbench.harness import reference_olmo_hybrid as reference
from chipbench.jobs.serve_generate import counters, offer, summarize
from chipbench.jobs.serve_loop import drive_decode_program
from chipbench.jobs.serve_moe import build_server, traced_stretch
from chipbench.jobs.serve_state import (Sampler, _rel, build_model,
                                        forced_plan, soak)

__all__ = ["build_model", "build_server", "check_programs", "check_engine",
           "counters", "offer", "summarize", "run"]

# System (bfloat16 weights and activations; float32 projections into the
# recurrence, state, RMSNorm, softmax and logits) against the float32
# reference at precision "highest" ON THE SAME bfloat16-rounded weights,
# max |a - b| over max |b|.  Each limit lies between two readings (on a
# TPU v5e; PERF.md section 6 has the runs): the largest the system gave over
# twelve runs of eleven seeds, and what the reference itself gives computed
# in a precision below the configuration's, which comes out as not correct
# by each limit (``chipbench/delta_control.py``: every layer's matrices
# rounded to float8_e4m3, as ``chipbench/precision.py`` rounds them):
#  - last-token prefill logits (16 layers of bfloat16 matmuls): system
#    0.0060-0.0080, float8 0.258-0.261;
#  - the FIRST linear layer's matrix state, as a prefill installs it
#    and as the decode program leaves it in EVERY live slot after the
#    forced steps (the other slots against that layer alone): its
#    inputs are the embedding's rows, exact in float32, and nothing is
#    rounded on the way into the state, so the system is the reference
#    to float32's rounding of two orders of summation (the chunks' solve
#    against one token at a time): system 1.4e-5-1.7e-4, float8
#    0.063-0.078, and a state KEPT in bfloat16 (the reference with its
#    state rounded to bfloat16 after every token, ``state_control``)
#    0.0175-0.0251;
#  - every linear layer's state (inputs that came through bfloat16
#    activations): system 0.034-0.119, float8 0.86-1.17;
#  - every linear layer's conv inputs: system <= 0.0125, float8
#    0.34-0.40;
#  - the FIRST full layer's K and V rows: system 0.0097-0.032, float8
#    0.27-0.43; a row in the wrong column reads near 1;
#  - the later full layers' K and V rows, behind 8-12 more layers of
#    bfloat16 activations, as ||a - b|| / ||b|| over every position:
#    system 0.0073-0.017, float8 0.28-0.35; a row in the wrong column
#    reads ~1.4.  Their largest single deviation (``deep_rows_peak``, a
#    reading) is 0.0097-0.215 where float8 reads 0.34-0.67, too close
#    for a limit with room: it sits at the first one or two positions
#    (``deep_rows_at``), where a linear head's output is v_0 scaled by
#    q_0 . k_0 and then normalised, so its sign follows that of a dot
#    product bfloat16's rounding may flip.
# The decode program hands back tokens, not logits: a token is held to
# the reference's argmax wherever the reference's two largest logits
# differ by more than DECISIVE x max |logit| (9-164 positions a run), and
# the run is refused if fewer than the cell's ``min_decisive`` are.
LOGIT_TOL = 0.05
FIRST_STATE_TOL = 1e-3
STATE_TOL = 0.3
CONV_TOL = 0.05
ROWS_TOL = 0.1
DEEP_ROWS_TOL = 0.05
DECISIVE = 0.1
LIMITS = {"prefill_logit_err": LOGIT_TOL,
          "first_state_err": FIRST_STATE_TOL, "state_err": STATE_TOL,
          "conv_err": CONV_TOL, "rows_err": ROWS_TOL,
          "deep_rows_err": DEEP_ROWS_TOL}

_LAYER_FNS = {}


def _layer_fn(cfg, state_dtype):
    """``reference.layer`` jitted for ``cfg``, once a configuration and
    state dtype."""
    import jax
    key = (id(cfg), state_dtype)
    if key not in _LAYER_FNS:
        _LAYER_FNS[key] = jax.jit(
            lambda p, x, kind: reference.layer(
                reference.to_float32(p), x, kind, cfg, state_dtype),
            static_argnames="kind")
    return _LAYER_FNS[key]


def reference_pass(model, tokens, head_rows, state_dtype=None):
    """The reference over ``tokens`` at their own length (a linear
    layer's state is the state after the LAST token), a layer at a time
    so that one layer's float32 matrices are all that is added to the
    device: (logits of the rows ``head_rows``, what each layer holds).
    ``model`` needs ``params`` and ``cfg`` only."""
    import jax.numpy as jnp
    cfg = model.cfg
    layer = _layer_fn(cfg, state_dtype)
    x = jnp.asarray(model.params["embed"][jnp.asarray(tokens)], jnp.float32)
    held = []
    for kind, p in zip(cfg["kinds"], model.params["layers"]):
        x, h = layer(p, x, kind=kind)
        held.append(tuple(np.asarray(a) for a in h))
    x = x[jnp.asarray(head_rows)]
    # an eighth of the vocabulary at a time, each fetched before the
    # next is asked for (a float32 copy of the whole head is 1.5 GB)
    head = model.params["head"]
    size = -(-head.shape[0] // 8)
    return np.concatenate(
        [np.asarray(reference.lm_logits(head[i:i + size],
                                        model.params["lnf_g"], x,
                                        cfg["rms_norm_eps"]))
         for i in range(0, head.shape[0], size)], axis=-1), held


# What a sequence of n tokens leaves in a cache, in one form for the
# system's slot and for the reference: every linear layer's matrix
# state (heads, dk, dv) and conv inputs (conv_size - 1, channels), every
# full layer's K and V rows (n, heads d), float32.

def reference_holding(held, n, cfg):
    lin = [h for k, h in zip(cfg["kinds"], held) if k == "linear"]
    full = [h for k, h in zip(cfg["kinds"], held) if k == "full"]
    return {"state": [s for _, s in lin], "conv": [c for c, _ in lin],
            "rows": [a[:n].reshape(n, -1) for kv in full for a in kv]}


def slot_holding(states, convs, rows, n, cfg):
    """``states``: a linear layer's matrix state of the slot in the
    cache's lanes (dk, heads dv); ``convs``: its conv inputs; ``rows``:
    K then V of each full layer, (positions, ...) positions leading."""
    from mxnet_tpu.ops.pallas.delta_step import from_lanes
    return {"state": [np.asarray(from_lanes(np.asarray(s, np.float32),
                                            cfg["lin_heads"]))
                      for s in states],
            "conv": [np.asarray(c, np.float32) for c in convs],
            "rows": [np.asarray(a[:n], np.float32).reshape(n, -1)
                     for a in rows]}


def _norm_rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def holding_errs(got, want):
    """The readings LIMITS holds: of the rows, the first full layer's K
    and V by their largest deviation, the later ones' by the norm over
    their positions (the module comment)."""
    return {"first_state_err": [_rel(got["state"][0], want["state"][0])],
            "state_err": [max(_rel(a, b) for a, b in zip(got["state"],
                                                         want["state"]))],
            "conv_err": [max(_rel(a, b) for a, b in zip(got["conv"],
                                                        want["conv"]))],
            "rows_err": [_rel(a, b) for a, b in zip(got["rows"][:2],
                                                    want["rows"][:2])],
            "deep_rows_err": [_norm_rel(a, b) for a, b in
                              zip(got["rows"][2:], want["rows"][2:])]}


def deep_rows_peaks(got, want):
    """The later full layers' K and V by their largest deviation, and
    the position it is at: readings without a limit."""
    peaks, at = [], []
    for a, b in zip(got["rows"][2:], want["rows"][2:]):
        peaks.append(_rel(a, b))
        at.append(int(np.unravel_index(np.argmax(np.abs(a - b)),
                                       a.shape)[0]))
    return peaks, at


def first_layer_errs(model, seqs, states, convs):
    """The first linear layer's matrix state and conv inputs of each of
    ``seqs`` (a slot's prompt and forced tokens) as the slot holds them
    (``states`` in the cache's lanes) against that layer of the
    reference alone, ``(state errors, conv errors)``.  The sequences
    are set after zero rows to one length, for one program: a zero row
    projects to zero conv inputs (the conv's own zero history) and to
    k = 0, which leaves the state at zero, so the padding changes
    nothing."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.delta_step import from_lanes
    cfg = model.cfg
    layer = _layer_fn(cfg, None)
    p0 = model.params["layers"][cfg["kinds"].index("linear")]
    embed = model.params["embed"]
    n = max(map(len, seqs))
    state_errs, conv_errs = [], []
    for seq, s, c in zip(seqs, states, convs):
        x = jnp.zeros((n, embed.shape[1]), jnp.float32).at[n - len(seq):] \
            .set(embed[jnp.asarray(seq)].astype(jnp.float32))
        _, (tail, S) = layer(p0, x, kind="linear")
        state_errs.append(_rel(from_lanes(np.asarray(s, np.float32),
                                          cfg["lin_heads"]), S))
        conv_errs.append(_rel(c, tail))
    return state_errs, conv_errs


def decisive_rows(want):
    """Rows of the reference's logits whose argmax a rounding cannot
    move."""
    top2 = np.sort(want, axis=-1)[:, -2:]
    return (top2[:, 1] - top2[:, 0]) > DECISIVE * np.abs(want).max()


def verdict(readings, min_decisive):
    """(correct, the names of what refuses): every error under its limit,
    every decisive token the reference's, and enough of them."""
    refused = [name for name, limit in LIMITS.items()
               if max(readings[name], default=0.0) > limit]
    if readings["decisive_mismatches"]:
        refused.append("decisive_mismatches")
    if readings["decisive_positions"] < min_decisive:
        refused.append("decisive_positions")
    return not refused, refused


def _slot_in_cache(cache, slot, n):
    """The slot's holding as the cache has it."""
    rows = [a for i in range(cache.n_rows)
            for a in (np.asarray(cache.k(i)[slot]).T,
                      np.asarray(cache.v(i)[slot]).T)]
    return (
        [np.asarray(s[slot]) for s in cache.state["ssm"]],
        [np.asarray(c[slot]) for c in cache.state["conv"]], rows, n)


def check_programs(model, shape, cell, rng, vocab):
    """What the timed programs produce, against the reference's full
    forward pass, BEFORE the engine holds its cache (two caches and the
    reference do not fit beside the weights): prefill at each of the
    check's lengths (logits, and every state, conv tail and row it
    hands the admission write); the decode program driven directly with
    every slot live, forced tokens in (the compared slots' tokens
    wherever the reference is decisive, and what it leaves in them and
    in every other slot's first linear layer; the longer prompts cross
    the rows' first bucket and the ragged kernel's 512-position
    blocks)."""
    max_slots, grid, prompt_buckets = shape
    spec, cfg = cell["check"], model.cfg
    readings = {name: [] for name in LIMITS}
    readings.update(deep_rows_peak=[], deep_rows_at=[])

    def add(got, want):
        for name, values in holding_errs(got, want).items():
            readings[name] += values
        peaks, at = deep_rows_peaks(got, want)
        readings["deep_rows_peak"] += peaks
        readings["deep_rows_at"] += at

    for n in spec["prompt_lengths"]:
        p = rng.integers(0, vocab, n, dtype=np.int32)
        bucket = min(b for b in prompt_buckets if b >= n)
        got, ks, vs, state = model.prefill(p, bucket)
        want, held = reference_pass(model, p, [n - 1])
        readings["prefill_logit_err"].append(_rel(got, want[0]))
        rows = [a for kv in zip(ks, vs) for a in kv]
        add(slot_holding(state["ssm"], state["conv"], rows, n, cfg),
            reference_holding(held, n, cfg))
        del ks, vs, state, held

    prompts, forced, compared = forced_plan(spec, max_slots, rng, vocab)
    cache = model.make_cache(max_slots, grid)
    for slot, p in enumerate(prompts):
        bucket = min(b for b in prompt_buckets if b >= len(p))
        _, ks, vs, state = model.prefill(p, bucket)
        cache.write_prompt(slot, ks, vs, len(p), state=state)
        del ks, vs, state
    answers = drive_decode_program(model, cache, forced)
    steps = len(forced)
    left = {slot: _slot_in_cache(cache, slot, len(prompts[slot]) + steps)
            for slot in compared}
    # the first linear layer of every other live slot
    others = [s for s in range(max_slots) if s not in compared]
    states = np.asarray(cache.state["ssm"][0])[others]
    convs = np.asarray(cache.state["conv"][0])[others]
    grown_to = int(cache.bucket)
    del cache
    gc.collect()
    state_errs, conv_errs = first_layer_errs(
        model, [np.concatenate([prompts[s], forced[:, s]]) for s in others],
        states, convs)
    readings["first_state_err"] += state_errs
    readings["conv_err"] += conv_errs
    del states, convs
    decisive_n = mismatches = 0
    for slot in compared:
        t0 = len(prompts[slot])
        want, held = reference_pass(
            model, np.concatenate([prompts[slot], forced[:, slot]]),
            np.arange(t0, t0 + steps))
        decisive = decisive_rows(want)
        decisive_n += int(decisive.sum())
        mismatches += int((answers[:, slot] != want.argmax(-1))[decisive]
                          .sum())
        add(slot_holding(*left[slot], cfg),
            reference_holding(held, t0 + steps, cfg))
    readings.update(decisive_positions=decisive_n,
                    decisive_mismatches=mismatches,
                    forced_bucket=grown_to)
    return readings


def check_engine(server, engine, model, cell, rng, vocab, readings):
    """Through the engine: admission, the scheduler, the streams.
    Greedy decoding alone against the reference's argmax at the decisive
    positions, and the same request in a full batch."""
    spec = cell["check"]
    n_new = spec["new_tokens"]
    prompt = rng.integers(0, vocab, spec["decode_prompt"], dtype=np.int32)

    def greedy(p):
        return server.generate(p, max_new_tokens=n_new, method="greedy")

    alone = greedy(prompt).result()
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(alone))
    want, _ = reference_pass(model, np.concatenate([prompt, alone[:-1]]), at)
    decisive = decisive_rows(want)
    readings["decisive_positions"] += int(decisive.sum())
    mismatches = int((np.asarray(alone) != want.argmax(-1))[decisive].sum())
    # the same request inside a full batch of other prompts: greedy
    # sequences part for good at the first token a rounding moves, so
    # they are held to each other up to the first indecisive position
    others = [rng.integers(0, vocab, int(n), dtype=np.int32)
              for n in rng.integers(*spec["batch_prompts"],
                                    engine.max_slots - 1)]
    streams = [greedy(p) for p in [prompt] + others]
    batched = [s.result() for s in streams][0]
    diff = next((i for i, (a, b) in enumerate(zip(alone, batched))
                 if a != b), None)
    if len(batched) != len(alone) or len(alone) != n_new \
            or (diff is not None and decisive[diff]):
        mismatches += 1
    readings["decisive_mismatches"] += mismatches
    readings["first_batch_difference"] = diff
    ok, refused = verdict(readings, spec["forced"]["min_decisive"])
    return dict(readings, ok=ok, refused=refused)


def state_control(model, spec, rng, vocab, state_dtype):
    """The check's state readings with the reference's state rounded to
    ``state_dtype`` after every token standing in for the system, on the
    check's own sequences (its prefill lengths, and its forced prompts
    with their forced tokens), through ``verdict``'s limits: (readings,
    the names of the limits it passes over).  The reading that says
    the first linear layer's limit would catch a state kept in
    ``state_dtype``."""
    cfg = model.cfg
    f = spec["forced"]
    readings = {"first_state_err": [], "state_err": []}
    for n in list(spec["prompt_lengths"]) + [p + f["steps"]
                                             for p in f["prompts"]]:
        p = rng.integers(0, vocab, n, dtype=np.int32)
        _, held = reference_pass(model, p, [n - 1])
        _, low = reference_pass(model, p, [n - 1], state_dtype)
        errs = holding_errs(reference_holding(low, n, cfg),
                            reference_holding(held, n, cfg))
        for name in readings:
            readings[name] += errs[name]
    return readings, [name for name in readings
                      if max(readings[name]) > LIMITS[name]]


def run(ctx):
    cell, config, seed = ctx["cell"], ctx["config"], ctx["seed"]
    arch, mix, seconds = config["arch"], cell["traffic"], ctx["seconds"]
    rng = np.random.default_rng(seed)
    import jax
    from mxnet_tpu import metrics, serving
    t = time.perf_counter()
    model = build_model(config, seed)
    build_s = time.perf_counter() - t
    # the engine's shape from the engine itself; its cache is given
    # back before the check allocates its own
    probe = serving.GenerationEngine(model, **cell["engine"])
    shape = (probe.max_slots, probe.grid, probe.prompt_buckets)
    del probe
    gc.collect()
    t = time.perf_counter()
    readings = check_programs(model, shape, cell, rng, arch["vocab"])
    check_s = time.perf_counter() - t
    server, engine, _, warmup_s = build_server(config, cell, seed, model)
    end_soak = None
    try:
        compiled = int(metrics.COMPILE_MISSES.value)
        loaded = int(metrics.COMPILE_PERSISTENT_HITS.value)
        check = check_engine(server, engine, model, cell, rng,
                             arch["vocab"], readings)
        trace = (cell["trace_at_s"], cell["trace_window_s"]) \
            if ctx["trace"] else None
        t_soak = time.perf_counter()
        cancel = soak(server, engine, mix, rng, arch["vocab"])
        soak_s = time.perf_counter() - t_soak
        # the slots are full two thirds into the ramp; the pilot's is
        # then given back to the traffic
        end_soak = threading.Timer(0.75 * float(mix["ramp_s"]), cancel)
        end_soak.start()
        with Sampler(engine, 0.02 if trace else 0.5) as sampler:
            out = offer(server, engine, mix, seconds, seed, arch["vocab"],
                        trace)
        cache = engine.cache
        cache_note = {
            "max_slots": cache.max_slots,
            "kinds": {k: cache.kinds.count(k) for k in set(cache.kinds)},
            "bytes": cache.bytes_by_kind(), "dtype": str(cache.dtype)}
    finally:
        if end_soak is not None:
            end_soak.cancel()
            end_soak.function()
        server.stop()
    seen, delta, red = summarize(out["loop"], mix, seconds), out["delta"], \
        out["reduction"]
    itemsize = jax.numpy.dtype(config["serve_dtype"]).itemsize
    positions, _, buckets = sampler.between(out["t0"], out["t0"] + seconds)
    breakdown, traced = None, {}
    if red is not None:
        breakdown = {
            "device_ops": trace_reduce.top(red["ops"]),
            "idle_gaps": trace_reduce.gaps_by_phase(
                red["gaps"], out["events"], red["offset_ns"]),
        }
        stretch, allocated, _ = sampler.between(
            *traced_stretch(out, trace, seconds))
        traced = {
            # what metrics/decode_hbm_pct.py reads: every layer's
            # weights and the head once, and what the live slots make a
            # step touch (state read and written, rows to the position)
            # in units of one full layer's K and V row
            "param_bytes": delta_bytes.step_weight_bytes(arch, itemsize),
            "live_kv_rows": delta_bytes.live_row_equivalents(
                stretch, arch, itemsize),
            # what metrics/decode_attn_roofline_pct.py and
            # metrics/delta_step_roofline_pct.py hold the ragged and the
            # delta kernels' calls of a step to
            "attn_bytes": delta_bytes.attn_bytes(stretch, arch, itemsize),
            "delta_state_bytes": delta_bytes.delta_state_bytes(stretch,
                                                               arch),
            "cache_bytes": float(np.mean(allocated)) if allocated
            else None,
        }
    return {
        "correct": check["ok"],
        "attempted": seen["attempted"],
        "failed": seen["failed"],
        "compiled_in_window": int(delta["compiles"]),
        "end_to_end": {
            "setup_s": out["t0"] - ctx["t_proc"],
            "serve_tokens_per_s": seen["tokens_per_s"],
        },
        "readings": dict(
            traced, warmup_s=warmup_s, delta=delta, lag_ms=seen["lag_ms"],
            kv_row_bytes=delta_bytes.row_bytes(arch, itemsize),
            max_slots=engine.max_slots),
        "trace": red,
        "breakdown": breakdown,
        "notes": {
            "check": check, "build_s": build_s, "check_s": check_s,
            "warmup_s": warmup_s, "soak_s": soak_s, "programs_warmed": engine.warmed,
            "programs_compiled": compiled, "programs_loaded": loaded,
            "requests": len(out["loop"].requests),
            "completed_per_s": seen["completed_per_s"],
            "backlog_at_end": out["backlog"],
            "ttft_ms_p50": traffic.percentile(seen["ttft_ms"], 0.5),
            "ttft_ms_p95": traffic.percentile(seen["ttft_ms"], 0.95),
            "itl_ms_p50": traffic.percentile(seen["itl_ms"], 0.5),
            "itl_ms_p95": traffic.percentile(seen["itl_ms"], 0.95),
            "generator_lag_ms_p95": traffic.percentile(seen["lag_ms"],
                                                       0.95),
            "kv_buckets_in_window": sorted(set(buckets)),
            "window": {
                "prefills": int(delta["prefill_n"]),
                "iterations": int(delta["iterations"]),
                "slots_live_mean": float(np.mean(
                    [len(p) for p in positions])) if positions else None,
                "position_mean": float(np.mean(np.concatenate(positions)))
                if positions and sum(map(len, positions)) else None,
            },
            "cache": cache_note,
            "traced": traced,
            "errors": seen["errors"],
        },
    }
