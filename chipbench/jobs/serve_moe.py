"""Job kind ``serve_moe``: a zoo LM whose layers route tokens over sparse
experts of which the model holds a share (the Command A+ family:
``cohere2_moe``) behind the continuous-batching engine, built as
tools/serve.py builds it (``DecodeModel.from_block`` ->
``GenerationEngine`` -> ``GenerationServer``) and driven in-process by
open-loop traffic.  The load, the clients' view and the counters are
``serve_generate``'s, the soak and the sampler ``serve_state``'s, all
unchanged, so ``chipbench/sweep.py`` and ``chipbench/precision.py`` work
on a cell of this kind as they are.

Cell file keys: as ``serve_state``.  In ``check.forced`` a prompt longer
than the model prefills (``prompts`` entry 4064) marks a slot that is
INSTALLED FROM THE REFERENCE: the reference's K (rotated) and V of a
random sequence of that length go into the slot's rings and rows, and
the forced steps then carry it across the window (position 4096), where
the ring wraps and the rows pass the window.  Bringing a slot there
through the system alone would take ~3000 decode steps (~55 s) in every
run; what the steps before it would have added is the system's own
rounding, which the other compared slots carry.
Configuration keys: ``zoo``, ``zoo_args``, ``zoo_kwargs`` (with the
serving dtype), ``serve_dtype``, the ``arch`` group.
"""
import gc
import threading
import time

import numpy as np

from chipbench.harness import (expert_load, moe_bytes, trace_reduce,
                               traffic)
from chipbench.harness import reference_cohere2moe as reference
from chipbench.jobs.serve_generate import counters, offer, summarize
from chipbench.jobs.serve_state import Sampler, _rel, build_model, soak

__all__ = ["build_model", "build_server", "check_programs", "check_engine",
           "counters", "offer", "summarize", "run"]

# System (bfloat16 weights and activations; float32 router scores,
# softmax, LayerNorm and logits) against the float32 reference at
# precision "highest" ON THE SAME bfloat16-rounded weights, max |a - b|
# over max |b|.  Each limit lies between two readings (my chip runs,
# PR 32; PERF.md has the runs): the largest the system gave over its
# runs of other seeds, and what the reference itself gives with every
# layer's matrices rounded to float8_e4m3, the nearest precision below
# the configuration's, which ``chipbench/precision.py`` puts through
# ``verdict`` below and which comes out as not correct by each limit:
#  - last-token prefill logits (4 layers of bfloat16 matmuls): system
#    0.006-0.011 over ten runs, float8 0.094-0.125;
#  - K and V as the caches hold them, the last window layer's ring
#    (RING_TOL; K there is stored rotated) and the full layer's rows
#    (ROWS_TOL), after a prefill and after the forced steps, at the
#    positions the paragraph below leaves to compare: system
#    0.004-0.027, float8 0.127-0.244 (PERF.md section 6 has each
#    reading); a row in the wrong column, a window off by one or a key
#    rotated at the wrong position reads near 1.
# The decode program hands back tokens, not logits: a token is held to
# the reference's argmax wherever the reference's two largest logits
# differ by more than DECISIVE x max |logit| (2.6 times the largest
# logit error read), in EVERY slot (625-732 positions a run, none
# moved; the float8 control moves 3 of 72), and the run is refused if
# fewer than the cell's ``min_decisive`` are.
#
# ROUTING IS DISCONTINUOUS, and with these weights an expert's output
# is as large as the residual it is added to: one choice that falls the
# other way moves that token's hidden state by ~10 % and everything the
# later layers make of it (a prefill's K and V read 0.07-0.21 by max
# |a - b| over all positions on the v5e, where the float8 control reads
# 0.14-0.33: no limit separates them, PERF.md section 6, PR 32).  So
# the check says WHERE a choice can fall either way and holds
# everything else tight.  A (token, layer) is *unsettled* where a held
# expert's reference score lies within MARGIN of the top-k threshold
# (midway between the k-th and the (k+1)-th score): a bfloat16 ``h`` may
# put it on the other side.  A token is *tainted* at a layer once it was
# unsettled at an earlier one: its hidden state there may differ by a
# whole expert, so nothing downstream of it is compared.
#  - K and V of a layer are compared at the positions untainted at that
#    layer, the prefill's logits for a prompt whose last token no layer
#    leaves unsettled (the prompt is drawn again until it is: about one
#    draw in two), a decode token where the reference is decisive AND
#    its token is settled in every layer;
#  - the programs hand back the held experts' LOAD (layers, held),
#    prefill over its prompt and the decode step over all its slots,
#    and every slot has a reference pass, so the load the reference's
#    scores give is known exactly: by layer a program's load may differ
#    from it by at most the unsettled choices of the untainted tokens
#    of that call plus ``top_k`` for each tainted one (``route_excess``
#    beyond that refuses the run; ``route_moved`` counts what moved).
# An attention layer carries a tainted token's K and V to every later
# token, diluted by the number of keys: from a few dozen positions on
# that is under the rounding, and the limits below were read with it in.
LOGIT_TOL = 0.04
RING_TOL = 0.06
ROWS_TOL = 0.06
DECISIVE = 0.03
# scores are in (0, 1).  With every position compared a prefill's K and
# V read 0.056-0.082 at 0.001 and 0.002, 0.021-0.027 at 0.005 and
# 0.018-0.026 at 0.01 and 0.02 (three runs): the rounding's own largest
# is reached at 0.005, which leaves a token in three settled through all
# four layers
MARGIN = 0.005
LIMITS = {"prefill_logit_err": LOGIT_TOL, "ring_err": RING_TOL,
          "rows_err": ROWS_TOL}
# lengths the reference is compiled at; it is causal and routes token by
# token, so right-padding changes no earlier row
REF_LENGTHS = (1024, 4224)


def build_server(config, cell, seed, model=None):
    from mxnet_tpu import serving
    model = model or build_model(config, seed)
    engine = serving.GenerationEngine(model, **cell["engine"])
    t = time.perf_counter()
    server = serving.GenerationServer(engine=engine, warmup=True).start()
    return server, engine, model, time.perf_counter() - t


_LAYER_FNS = {}


def _layer_fn(cfg):
    """``reference.layer`` jitted for ``cfg``, once a configuration."""
    import jax
    if id(cfg) not in _LAYER_FNS:
        _LAYER_FNS[id(cfg)] = jax.jit(
            lambda p, x, kind: reference.layer(p, x, kind, cfg),
            static_argnames="kind")
    return _LAYER_FNS[id(cfg)]


def reference_pass(model, tokens, head_rows):
    """The reference over ``tokens`` (padded to one of REF_LENGTHS), a
    layer at a time so that one layer's float32 matrices are all that
    is added to the device: (logits of the rows ``head_rows``, what the
    layers hold: ``kv`` each layer's (k, v), k as a cache holds it, and
    ``scores`` each layer's router scores (tokens, experts)).  ``model``
    needs ``params`` and ``cfg`` only."""
    import jax
    import jax.numpy as jnp
    cfg, n = model.cfg, len(tokens)
    ids = np.zeros(min(L for L in REF_LENGTHS if L >= n), np.int32)
    ids[:n] = tokens
    step = _layer_fn(cfg)
    x = jnp.asarray(model.params["embed"][jnp.asarray(ids)], jnp.float32)
    kv, scores = [], []
    for kind, p in zip(cfg["kinds"], model.params["layers"]):
        x, held, s = step(p, x, kind=kind)
        kv.append(held)
        scores.append(np.asarray(s[:n]))
    with jax.default_matmul_precision("highest"):
        x = reference.layer_norm(
            x[jnp.asarray(head_rows)],
            jnp.asarray(model.params["lnf_g"], jnp.float32),
            cfg["layer_norm_eps"])
    return np.asarray(reference.lm_logits(model.params["embed"], x, cfg)), \
        {"kv": kv, "scores": scores}


def held_choices(scores, cfg):
    """From one layer's reference scores (rows, experts): which held
    experts each row chose (rows, held), and how many of them lie within
    MARGIN of its top-k threshold (rows,)."""
    lo, hi = cfg["experts_held"]
    k = cfg["top_k"]
    ranked = np.sort(scores, axis=-1)
    threshold = (ranked[:, -k] + ranked[:, -k - 1])[:, None] / 2
    here = scores[:, lo:hi]
    return here > threshold, \
        (np.abs(here - threshold) < MARGIN).sum(-1)


def tainted_at(scores, cfg):
    """(layers + 1, rows) bool: row r is tainted at layer L where some
    layer before L left it unsettled; the last row is "after every
    layer"."""
    near = np.stack([held_choices(s, cfg)[1] > 0 for s in scores])
    return np.concatenate([np.zeros((1, near.shape[1]), bool),
                           np.cumsum(near, axis=0) > 0])


# What a sequence of n tokens leaves in a cache, in one form for the
# system's slot and for the reference: the last window layer's K and V
# of the positions a query at n would still see, the last full layer's K
# and V of all n.

def _layers_of(cfg):
    kinds = list(cfg["kinds"])
    return (max(i for i, k in enumerate(kinds) if k == "window"),
            max(i for i, k in enumerate(kinds) if k == "full"))


def _flat(a, rows):
    return np.asarray(a, np.float32).reshape(len(a), -1)[rows]


def reference_holding(held, n, cfg):
    """With ``ring_ok`` / ``rows_ok``: the positions untainted at the
    layer, the only ones ``holding_errs`` compares."""
    window, full = _layers_of(cfg)
    seen = np.arange(max(0, n - cfg["window"]), n)
    tainted = tainted_at([s[:n] for s in held["scores"]], cfg)
    return {"ring": [_flat(a, seen) for a in held["kv"][window]],
            "rows": [_flat(a, np.arange(n)) for a in held["kv"][full]],
            "ring_ok": ~tainted[window][seen], "rows_ok": ~tainted[full]}


def slot_holding(rings, rows, n, cfg):
    """``rings``: the last window layer's K and V (kv, window), position
    p in column p % window; ``rows``: the last full layer's K and V
    (positions, kv)."""
    W = cfg["window"]
    seen = np.arange(max(0, n - W), n)
    return {"ring": [np.asarray(a, np.float32).T[seen % W] for a in rings],
            "rows": [_flat(a, np.arange(n)) for a in rows]}


def holding_errs(got, want):
    """max |a - b| over the positions ``want`` says are untainted, over
    max |b|; 1.0, which every limit refuses, where none is."""
    return {name + "_err": [
        float(np.abs(a - b)[want[name + "_ok"]].max() / np.abs(b).max())
        if want[name + "_ok"].any() else 1.0
        for a, b in zip(got[name], want[name])]
        for name in ("ring", "rows")}


def decisive_rows(want):
    """Rows of the reference's logits whose argmax a rounding cannot
    move."""
    top2 = np.sort(want, axis=-1)[:, -2:]
    return (top2[:, 1] - top2[:, 0]) > DECISIVE * np.abs(want).max()


def settled_rows(scores, cfg):
    """Rows (of every layer's ``scores``) that no layer leaves
    unsettled."""
    return ~tainted_at(scores, cfg)[-1]


def route_reading(load, scores, cfg):
    """One program call's load ``(layers, held)`` against the
    reference's router ``scores`` (a (rows, experts) array a layer, of
    the rows the call routed): (choices moved, allowed, excess), summed
    over layers (the module comment)."""
    tainted = tainted_at(scores, cfg)
    moved = allowed = excess = 0
    for layer, (got, s) in enumerate(zip(np.asarray(load), scores)):
        chosen, near = held_choices(s, cfg)
        diff = int(np.abs(got - chosen.sum(0)).sum())
        room = int(near[~tainted[layer]].sum()
                   + cfg["top_k"] * tainted[layer].sum())
        moved, allowed = moved + diff, allowed + room
        excess += max(0, diff - room)
    return moved, allowed, excess


def verdict(readings, min_decisive):
    """(correct, the names of what refuses): every error under its limit,
    every decisive token the reference's and enough of them, no load
    past what the unsettled choices allow."""
    refused = [name for name, limit in LIMITS.items()
               if max(readings[name]) > limit]
    if readings["decisive_mismatches"]:
        refused.append("decisive_mismatches")
    if readings["decisive_positions"] < min_decisive:
        refused.append("decisive_positions")
    if readings.get("route_excess"):
        refused.append("route_excess")
    return not refused, refused


def forced_plan(spec, max_slots, rng, vocab):
    """Every slot's prompt and forced tokens for the direct drive of the
    decode program, and the compared slots, spread over the slot axis."""
    f = spec["forced"]
    lengths = [n for n in f["prompts"] for _ in range(f["copies"])]
    compared = dict(zip(
        np.linspace(0, max_slots - 1, len(lengths)).astype(int).tolist(),
        lengths))
    prompts = [rng.integers(0, vocab, int(compared.get(
        slot, rng.integers(*spec["batch_prompts"]))), dtype=np.int32)
        for slot in range(max_slots)]
    forced = rng.integers(0, vocab, (f["steps"], max_slots), dtype=np.int32)
    return prompts, forced, sorted(compared)


def install_from_reference(cache, slot, held, n, cfg):
    """Put the reference's K and V of a sequence's first ``n`` positions
    into ``slot``: every window layer's ring (position p in column
    p % window) and every full layer's rows, through the cache's own
    admission write."""
    W, kinds = cfg["window"], list(cfg["kinds"])
    seen = np.arange(max(0, n - W), n)
    state, rows = {"wk": [], "wv": []}, ([], [])
    for kind, kv in zip(kinds, held["kv"]):
        for name, a, full in zip(("wk", "wv"), kv, rows):
            a = np.asarray(a, np.float32)
            if kind == "window":
                ring = np.zeros((a[0].size, W), np.float32)
                ring[:, seen % W] = a.reshape(len(a), -1)[seen].T
                state[name].append(ring)
            else:
                full.append(np.concatenate(
                    [a[:n], np.zeros((-n % 512,) + a.shape[1:],
                                     np.float32)]))
    cache.write_prompt(slot, rows[0], rows[1], n, state=state)


def drive_decode_program(model, cache, forced):
    """Run ``model.step`` (the program the window times, at the
    engine's slots, growing the rows as the engine does) over
    ``forced`` (steps, slots) whatever it answers.  Returns its answers
    (steps, slots) and each step's load (layers, held)."""
    answers, loads = [], []
    for toks in forced:
        cache.ensure_capacity(cache.needed_capacity())
        answers.append(model.step(cache, toks, cache.positions))
        loads.append(model.last_load.copy())
        cache.positions += 1
    return np.stack(answers), loads


def check_programs(model, shape, cell, rng, vocab):
    """What the timed programs produce, against the reference's full
    forward pass, BEFORE the engine holds its cache (two caches and the
    reference do not fit beside the weights): prefill (logits, what it
    installs, its load); the decode program driven directly with every
    slot live and referenced, forced tokens in (every slot's tokens
    wherever the reference is decisive and the token settled, its load
    every step, and what it leaves in the compared slots, of which
    those installed from the reference cross the window)."""
    max_slots, grid, prompt_buckets = shape
    spec, cfg = cell["check"], model.cfg
    n_window = list(cfg["kinds"]).count("window") - 1
    readings = {name: [] for name in LIMITS}
    routed = []          # (load, scores) of every program call

    def add(errs):
        for name, values in errs.items():
            readings[name] += values

    redrawn = 0
    for n in spec["prompt_lengths"]:
        while True:
            p = rng.integers(0, vocab, n, dtype=np.int32)
            want, held = reference_pass(model, p, [n - 1])
            if settled_rows(held["scores"], cfg)[-1]:
                break
            redrawn += 1
        bucket = min(b for b in prompt_buckets if b >= n)
        got, ks, vs, state = model.prefill(p, bucket)
        add({"prefill_logit_err": [_rel(got, want[0])]})
        add(holding_errs(
            slot_holding((state["wk"][n_window], state["wv"][n_window]),
                         (ks[-1], vs[-1]), n, cfg),
            reference_holding(held, n, cfg)))
        routed.append((model.last_prefill_load, held["scores"]))
        del ks, vs, state, held

    prompts, forced, compared = forced_plan(spec, max_slots, rng, vocab)
    steps = len(forced)
    # the engine's grid and one bucket a block past it, for the slots
    # that cross the window
    cache = model.make_cache(
        max_slots, tuple(grid) + (grid[-1] + min(grid[-1], 512),))
    want, firm, wanted, scores = [], [], {}, []
    for slot, p in enumerate(prompts):
        t0 = len(p)
        logits, held = reference_pass(
            model, np.concatenate([p, forced[:, slot]]),
            np.arange(t0, t0 + steps))
        # the rows the forced steps route: one token a step
        scores.append([s[t0:] for s in held["scores"]])
        # every slot's tokens are held to the reference's, where it is
        # decisive and the token settled
        want.append(logits.argmax(-1))
        firm.append(decisive_rows(logits) & settled_rows(scores[-1], cfg))
        if t0 > prompt_buckets[-1]:     # longer than the model prefills
            install_from_reference(cache, slot, held, t0, cfg)
        else:
            bucket = min(b for b in prompt_buckets if b >= t0)
            _, ks, vs, state = model.prefill(p, bucket)
            cache.write_prompt(slot, ks, vs, t0, state=state)
        if slot in compared:
            wanted[slot] = reference_holding(held, t0 + steps, cfg)
        del held, logits
    answers, loads = drive_decode_program(model, cache, forced)
    routed += [(load, [np.stack([s[layer][j] for s in scores])
                       for layer in range(len(cfg["kinds"]))])
               for j, load in enumerate(loads)]
    firm, want = np.stack(firm, axis=1), np.stack(want, axis=1)
    decisive_n = int(firm.sum())
    mismatches = int((answers != want)[firm].sum())
    for slot in compared:
        add(holding_errs(slot_holding(
            (cache.state["wk"][n_window][slot],
             cache.state["wv"][n_window][slot]),
            (np.asarray(cache.k(-1)[slot]).T, np.asarray(cache.v(-1)[slot]).T),
            len(prompts[slot]) + steps, cfg), wanted[slot]))
    crossed = sorted(int(cache.positions[s]) for s in compared
                     if cache.positions[s] > cfg["window"])
    del cache
    gc.collect()
    route = np.sum([route_reading(*call, cfg) for call in routed], axis=0)
    readings.update(
        decisive_positions=decisive_n, decisive_mismatches=mismatches,
        route_moved=int(route[0]), route_allowed=int(route[1]),
        route_excess=int(route[2]), crossed_window_at=crossed,
        prompts_redrawn=redrawn)
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
    want, held = reference_pass(
        model, np.concatenate([prompt, alone[:-1]]), at)
    decisive = decisive_rows(want) & settled_rows(
        [s[at] for s in held["scores"]], cfg=model.cfg)
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


def traced_stretch(out, trace, seconds):
    """[lo, hi] on the host's clock of the stretch ``offer`` traced."""
    at_s = min(trace[0], max(0.0, seconds - trace[1]) / 2)
    lo = out["t0"] + at_s
    return lo, lo + min(trace[1], seconds)


def run(ctx):
    cell, config, seed = ctx["cell"], ctx["config"], ctx["seed"]
    arch, mix, seconds = config["arch"], cell["traffic"], ctx["seconds"]
    rng = np.random.default_rng(seed)
    import jax
    from mxnet_tpu import metrics, serving
    from chipbench.harness import program_spans
    model = build_model(config, seed)
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
        # the slots are full well inside the ramp; the pilot's is then
        # given back to the traffic
        end_soak = threading.Timer(0.75 * float(mix["ramp_s"]), cancel)
        end_soak.start()
        with Sampler(engine, 0.02 if trace else 0.5) as sampler:
            out = offer(server, engine, mix, seconds, seed, arch["vocab"],
                        trace)
        cache = engine.cache
        cache_note = {
            "max_slots": cache.max_slots, "window": cache.window,
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
    in_window = expert_load.steps_between(out["t0"], out["t0"] + seconds)
    breakdown, traced = None, {}
    if red is not None:
        breakdown = {
            "device_ops": trace_reduce.top(red["ops"]),
            "idle_gaps": trace_reduce.gaps_by_phase(
                red["gaps"], out["events"], red["offset_ns"]),
        }
        lo, hi = traced_stretch(out, trace, seconds)
        positions, allocated, _ = sampler.between(lo, hi)
        hit = expert_load.share(expert_load.steps_between(lo, hi),
                                "experts_hit")
        prefills = [s.get("attrs", {}) for s in program_spans.resident(
            "model.prefill", lo, hi)]
        gmm = [moe_bytes.gmm_flops_and_bytes(
            a["expert_assignments"], a["experts_hit"], arch, itemsize)
            for a in prefills if "experts_hit" in a]
        if hit is not None:
            traced = {
                # what metrics/decode_hbm_pct.py reads: the weights a
                # step must read (every one outside the routed experts,
                # and the held experts the traced steps hit, a mean) and
                # the live K and V rows to the position
                "param_bytes": moe_bytes.step_weight_bytes(
                    hit * arch["layers"] * arch["experts_held"], arch,
                    itemsize),
                "live_kv_rows": moe_bytes.live_row_equivalents(positions,
                                                               arch),
                "cache_bytes": float(np.mean(allocated))
                if allocated else None,
                "gmm_flops": sum(f for f, _ in gmm),
                "gmm_bytes": sum(b for _, b in gmm),
                "traced_prefills": len(gmm),
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
            kv_row_bytes=moe_bytes.row_bytes(arch, itemsize),
            max_slots=engine.max_slots),
        "trace": red,
        "breakdown": breakdown,
        "notes": {
            "check": check, "check_s": check_s, "warmup_s": warmup_s,
            "soak_s": soak_s, "programs_warmed": engine.warmed,
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
            "kv_buckets_in_window": sorted(set(sampler.between(
                out["t0"], out["t0"] + seconds)[2])),
            "cache": cache_note,
            "expert_tokens_mean": expert_load.share(in_window,
                                                    "expert_assignments"),
            "experts_hit_share": expert_load.share(in_window,
                                                   "experts_hit"),
            "traced": traced,
            "errors": seen["errors"],
        },
    }
