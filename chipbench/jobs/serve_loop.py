"""Job kind ``serve_loop``: a zoo LM whose one stack of layers runs several
times a token (the Ouro family: ``gluon.model_zoo.ouro``) behind the
continuous-batching engine, built as tools/serve.py builds it
(``DecodeModel.from_block`` -> ``GenerationEngine`` ->
``GenerationServer``) and driven in-process by open-loop traffic.  The
load, the clients' view and the counters are ``serve_generate``'s, the
sampler ``serve_state``'s, the server's build ``serve_moe``'s, all
unchanged, so ``chipbench/sweep.py`` and ``chipbench/precision.py`` work
on a cell of this kind as they are.

Cell file keys: as ``serve_state``, without a soak (a cell of this kind
has one KV bucket).  In ``check.forced`` a prompt longer than the
traffic's longest (``traffic.prompt.max``) marks a slot that is
INSTALLED FROM THE REFERENCE: the reference's K (rotated) and V of a
random sequence of that length, rounded to the cache's dtype, go into
every entry of the slot through the cache's own admission write, and
the forced steps then carry it across the ragged kernel's 512-position
block.  What the system's own prefill would have put there is compared
at the prefill lengths.
Configuration keys: ``zoo``, ``zoo_args``, ``zoo_kwargs`` (with the
serving dtype), ``serve_dtype``, the ``arch`` group.
"""
import gc
import time

import numpy as np

from chipbench.harness import loop_bytes, trace_reduce, traffic
from chipbench.harness import reference_ouro as reference
from chipbench.jobs.serve_generate import counters, offer, summarize
from chipbench.jobs.serve_moe import build_server, traced_stretch
from chipbench.jobs.serve_state import (Sampler, _rel, build_model,
                                        forced_plan)

__all__ = ["build_model", "build_server", "check_programs", "check_engine",
           "counters", "offer", "summarize", "run"]

# System (bfloat16 weights and activations; float32 RMSNorm, softmax and
# logits) against the float32 reference at precision "highest" ON THE
# SAME bfloat16-rounded weights, max |a - b| over max |b|.  Each limit
# lies between two readings (my chip runs, PR 35; PERF.md section 6 has
# every run): the largest the system gave over its runs of other seeds,
# and what the reference itself gives with every layer's matrices
# rounded to float8_e4m3, the nearest precision below the
# configuration's, which ``chipbench/precision.py`` puts through
# ``verdict`` below and which comes out as not correct by each limit:
#  - K and V as the cache holds them in entries (0, 0), the first, and
#    (1, 0), the first that a wrong entry index would alias, after a
#    prefill and after the forced steps, over ALL positions: system
#    0.003-0.027 over 21 runs (entry (0, 0) 0.003-0.007), float8
#    0.030-0.035 in (0, 0) and 0.20-0.53 in (1, 0); a row in the wrong
#    column or the wrong entry reads near 1;
#  - last-token prefill logits: system 0.009-0.048, float8 0.27-1.85.
#
# THE LOOPED MAP CARRIES SOME SEQUENCES AWAY.  An untrained stack run
# four times over, renormed after every step, amplifies a perturbation
# unevenly, by the sequence: in the LAST entry (3, 47) a position's K
# or V row reads ~0.013 like the other entries in most arrays, but in
# about one array in ten a run of positions (often every position a
# slot decoded: 58, 68 and 47 of three slots' 64) reads 0.05-0.14, and
# with branch-output gains of 1/4 single positions read up to 1.2
# (never in (0, 0) or (1, 0); prefill alone shows it too): there the
# bfloat16 system and the float32 reference have parted (PERF.md
# section 6, PR 35).  The float8 control reads 0.25-0.58 there in EVERY
# array, at every position.  No limit on a maximum over positions
# separates the two with room, so the check says WHERE a position has
# parted and holds everything else tight: a position is *unsettled*
# where its K or V row of the last entry lies further than SETTLED
# from the reference's.  The share of unsettled positions among ALL
# the run's compared positions is itself limited (UNSETTLED_MAX:
# system at most 0.03 over 26 runs, float8 1.0; a dropped pass or a
# wrong entry unsettles every position, a fault of the decode program
# alone the 17 % of them that it wrote), last-token logits are compared
# for a prompt whose last position is settled (the prompt is drawn
# again otherwise, as ``serve_moe`` redraws), and a token only where
# the reference is decisive AND the position settled.
# The decode program hands back tokens, not logits: a token is held to
# the reference's argmax wherever the reference's two largest logits
# differ by more than DECISIVE x max |logit| (the largest logit error
# read), in EVERY slot (8-337 positions a run, none moved in 19 runs),
# and the run is refused if fewer than the cell's ``min_decisive`` are.
LOGIT_TOL = 0.15
ROWS_TOL = 0.07
SETTLED = 0.05
UNSETTLED_MAX = 0.12
DECISIVE = 0.05
LIMITS = {"prefill_logit_err": LOGIT_TOL, "rows_err": ROWS_TOL,
          "unsettled_share": UNSETTLED_MAX}
# prompts drawn for one prefill check before it is given up (reading 1)
DRAWS = 4
# lengths the reference is compiled at; it is causal, so right-padding
# changes no earlier row
REF_LENGTHS = (256, 640)


def compared_entries(cfg):
    """The cache entries the check reads: passes (0, 0), (1, 0) and the
    last."""
    n = cfg["num_layers"]
    return (0, n, cfg["loop_steps"] * n - 1)


_PASS_FNS = {}


def _pass_fns(cfg):
    """``reference.layer`` on layer ``l`` of the stacked weights and
    ``reference.loop_end``, jitted for ``cfg`` once a configuration: ONE
    layer program serves every pass of every loop step."""
    import jax
    if id(cfg) not in _PASS_FNS:
        _PASS_FNS[id(cfg)] = (
            jax.jit(lambda layers, l, x: reference.layer(
                {name: a[l] for name, a in layers.items()}, x, cfg)),
            jax.jit(lambda params, x: reference.loop_end(params, x, cfg)))
    return _PASS_FNS[id(cfg)]


def reference_pass(model, tokens, head_rows, keep_all=None):
    """The reference over ``tokens`` (padded to one of REF_LENGTHS), a
    pass at a time so that one layer's float32 matrices are all that is
    added to the device: (logits of the rows ``head_rows``, what the
    passes hold: ``kept`` {entry: (k, v) float32 (tokens, channels)} for
    the compared entries and, with ``keep_all`` a dtype, ``all``: every
    entry's (K, V) stacked ``(entries, tokens, heads, d)`` rounded to
    it).  ``model`` needs ``params`` and ``cfg`` only."""
    import jax.numpy as jnp
    cfg, n = model.cfg, len(tokens)
    ids = np.zeros(min(L for L in REF_LENGTHS if L >= n), np.int32)
    ids[:n] = tokens
    layer, loop_end = _pass_fns(cfg)
    x = jnp.asarray(model.params["embed"][jnp.asarray(ids)], jnp.float32)
    watched, kept = compared_entries(cfg), {}
    # on the host: 192 entries of a 544-token sequence are 0.43 GB a
    # side, beside a cache that is already most of the chip
    every = None if keep_all is None else tuple(
        np.zeros((cfg["loop_steps"] * cfg["num_layers"], n,
                  cfg["num_heads"], cfg["head_dim"]), keep_all)
        for _ in range(2))
    for t in range(cfg["loop_steps"]):
        for l in range(cfg["num_layers"]):
            x, k, v = layer(model.params["layers"], np.int32(l), x)
            entry = t * cfg["num_layers"] + l
            if entry in watched:
                kept[entry] = tuple(np.asarray(a[:n]).reshape(n, -1)
                                    for a in (k, v))
            if every is not None:
                every[0][entry] = np.asarray(k[:n].astype(keep_all))
                every[1][entry] = np.asarray(v[:n].astype(keep_all))
        x = loop_end(model.params, x)
    logits = np.asarray(reference.lm_logits(
        model.params["head"], x[jnp.asarray(head_rows)]))
    return logits, {"kept": kept, "all": every}


# What a sequence of n tokens leaves in the compared entries, in one
# form for the system's slot and for the reference: K then V of each,
# (n, channels) float32.

def reference_holding(held, n, cfg):
    return {"rows": [a[:n] for entry in compared_entries(cfg)
                     for a in held["kept"][entry]]}


def slot_holding(rows_of, n, cfg):
    """``rows_of(entry)``: that entry's (K, V) of the slot, each
    (positions, ...) with the positions leading."""
    return {"rows": [np.asarray(a[:n], np.float32).reshape(n, -1)
                     for entry in compared_entries(cfg)
                     for a in rows_of(entry)]}


def unsettled(got, want):
    """(positions,) bool: the positions whose K or V row of the LAST
    compared entry lies further than SETTLED from the reference's (the
    module comment)."""
    return last_entry_errs(got, want) > SETTLED


def last_entry_errs(got, want):
    """(positions,): each position's K or V row of the LAST compared
    entry against the reference's, the larger of the two."""
    return np.maximum(*(
        np.abs(a - b).max(-1) / np.abs(b).max()
        for a, b in zip(got["rows"][-2:], want["rows"][-2:])))


def holding_errs(got, want):
    """The first two entries over all positions; of the last, which
    positions are unsettled: 1.0 or 0.0 a position, so that ``verdict``
    takes their share over everything a run compared."""
    return {"rows_err": [_rel(a, b) for a, b in zip(got["rows"][:-2],
                                                    want["rows"][:-2])],
            "unsettled_share": unsettled(got, want).astype(float).tolist()}


def decisive_rows(want):
    """Rows of the reference's logits whose argmax a rounding cannot
    move."""
    top2 = np.sort(want, axis=-1)[:, -2:]
    return (top2[:, 1] - top2[:, 0]) > DECISIVE * np.abs(want).max()


def verdict(readings, min_decisive):
    """(correct, the names of what refuses): every error under its limit
    (of ``unsettled_share``, a 1.0 or 0.0 a compared position, the
    mean), every decisive token the reference's, and enough of them."""
    refused = [name for name, limit in LIMITS.items()
               if (np.mean if name == "unsettled_share" else max)(
                   readings[name]) > limit]
    if readings["decisive_mismatches"]:
        refused.append("decisive_mismatches")
    if readings["decisive_positions"] < min_decisive:
        refused.append("decisive_positions")
    return not refused, refused


def drive_decode_program(model, cache, forced):
    """Run ``model.step`` (the program the window times, at the
    engine's slots and bucket) over ``forced`` (steps, slots) whatever
    it answers.  Returns its answers (steps, slots)."""
    answers = []
    for toks in forced:
        cache.ensure_capacity(cache.needed_capacity())
        answers.append(model.step(cache, toks, cache.positions))
        cache.positions += 1
    return np.stack(answers)


def check_programs(model, shape, cell, rng, vocab):
    """What the timed programs produce, against the reference's full
    forward pass, BEFORE the engine holds its cache (two caches do not
    fit beside the weights): prefill (logits, and the rows it hands the
    admission write in the compared entries); the decode program driven
    directly with every slot live and referenced, forced tokens in
    (every slot's tokens wherever the reference is decisive and the
    position settled, and what it leaves in every slot, of which those
    installed from the reference cross the kernel's 512-position
    block)."""
    max_slots, grid, prompt_buckets = shape
    spec, cfg = cell["check"], model.cfg
    longest = int(cell["traffic"]["prompt"]["max"])
    readings = {name: [] for name in LIMITS}

    parted = []     # every position's error in the last entry

    def add(got, want):
        for name, values in holding_errs(got, want).items():
            readings[name] += values
        parted.append(last_entry_errs(got, want))

    redrawn = 0
    for n in spec["prompt_lengths"]:
        logit_err = 1.0
        for _ in range(DRAWS):
            p = rng.integers(0, vocab, n, dtype=np.int32)
            want, held = reference_pass(model, p, [n - 1])
            bucket = min(b for b in prompt_buckets if b >= n)
            got, ks, vs = model.prefill(p, bucket)
            holding = (slot_holding(lambda e: (ks[0][e], vs[0][e]), n, cfg),
                       reference_holding(held, n, cfg))
            add(*holding)
            del ks, vs, held
            if not unsettled(*holding)[-1]:
                logit_err = _rel(got, want[0])
                break
            redrawn += 1
        readings["prefill_logit_err"].append(logit_err)

    prompts, forced, compared = forced_plan(spec, max_slots, rng, vocab)
    steps = len(forced)
    cache = model.make_cache(max_slots, grid)
    want, firm, wanted = [], [], []
    for slot, p in enumerate(prompts):
        t0 = len(p)
        installed = t0 > longest
        logits, held = reference_pass(
            model, np.concatenate([p, forced[:, slot]]),
            np.arange(t0, t0 + steps),
            keep_all=cache.dtype if installed else None)
        want.append(logits.argmax(-1))
        firm.append(decisive_rows(logits))
        if installed:
            pad = ((0, 0), (0, -t0 % 128), (0, 0), (0, 0))
            cache.write_prompt(
                slot, *([np.pad(a[:, :t0], pad)] for a in held["all"]), t0)
        else:
            bucket = min(b for b in prompt_buckets if b >= t0)
            _, ks, vs = model.prefill(p, bucket)
            cache.write_prompt(slot, ks, vs, t0)
            del ks, vs
        wanted.append(reference_holding(held, t0 + steps, cfg))
        del held, logits
    answers = drive_decode_program(model, cache, forced)
    for slot, p in enumerate(prompts):
        holding = (slot_holding(
            lambda e, s=slot: (np.asarray(cache.k(e)[s]).T,
                               np.asarray(cache.v(e)[s]).T),
            len(p) + steps, cfg), wanted[slot])
        add(*holding)
        # every slot's tokens are held to the reference's, where it is
        # decisive and the token's position settled
        firm[slot] &= ~unsettled(*holding)[len(p):]
    firm, want = np.stack(firm, axis=1), np.stack(want, axis=1)
    block = min(512, grid[0])
    crossed = sorted(int(cache.positions[s]) for s in compared
                     if len(prompts[s]) < block < cache.positions[s])
    del cache
    gc.collect()
    readings.update(
        decisive_positions=int(firm.sum()),
        decisive_mismatches=int((answers != want)[firm].sum()),
        crossed_block_at=crossed, prompts_redrawn=redrawn,
        unsettled_by_array=[float((e > SETTLED).mean()) for e in parted],
        # the last entry's errors over all compared positions: median,
        # 90th, 99th percentile and the largest
        last_entry_err_quantiles=[float(q) for q in np.quantile(
            np.concatenate(parted), (0.5, 0.9, 0.99, 1.0))])
    return readings


def _served(server, engine, model, tokens, n_new):
    """``n_new`` greedy tokens for ``tokens`` through the engine, served
    into an EMPTY engine's first slot (and what else ``tokens`` holds:
    the rest of a batch, behind it): (its tokens, the reference's logits
    at their positions, which of those positions are unsettled by the
    rows the slot holds afterwards)."""
    prompt, *others = tokens
    while engine.cache.occupancy():
        time.sleep(0.01)
    slot = engine.cache.free_slots()[0]
    streams = [server.generate(p, max_new_tokens=n_new, method="greedy")
               for p in [prompt] + others]
    out = [s.result() for s in streams][0]
    while engine.cache.occupancy():
        time.sleep(0.01)
    seq = np.concatenate([prompt, out[:-1]])
    at = np.arange(len(prompt) - 1, len(seq))
    want, held = reference_pass(model, seq, at)
    cache = engine.cache        # idle: nothing launches over its buffers
    parted = unsettled(
        slot_holding(lambda e: (np.asarray(cache.k(e)[slot]).T,
                                np.asarray(cache.v(e)[slot]).T),
                     len(seq), model.cfg),
        reference_holding(held, len(seq), model.cfg))
    return np.asarray(out), want, parted[at], parted.astype(float).tolist()


def check_engine(server, engine, model, cell, rng, vocab, readings):
    """Through the engine: admission, the scheduler, the streams.
    Greedy decoding alone against the reference's argmax at the decisive
    positions that are settled, and the same request in a full batch."""
    spec = cell["check"]
    n_new = spec["new_tokens"]
    prompt = rng.integers(0, vocab, spec["decode_prompt"], dtype=np.int32)
    alone, want, parted, share = _served(server, engine, model, [prompt],
                                         n_new)
    firm = decisive_rows(want) & ~parted
    readings["decisive_positions"] += int(firm.sum())
    mismatches = int((alone != want.argmax(-1))[firm].sum())
    # the same request inside a full batch of other prompts: greedy
    # sequences part for good at the first token a rounding moves, so
    # they are held to each other up to the first position that is
    # indecisive or unsettled in either
    others = [rng.integers(0, vocab, int(n), dtype=np.int32)
              for n in rng.integers(*spec["batch_prompts"],
                                    engine.max_slots - 1)]
    batched, _, parted_b, share_b = _served(server, engine, model,
                                            [prompt] + others, n_new)
    diff = next((i for i, (a, b) in enumerate(zip(alone, batched))
                 if a != b), None)
    if len(batched) != len(alone) or len(alone) != n_new \
            or (diff is not None and firm[diff] and not parted_b[diff]):
        mismatches += 1
    readings["decisive_mismatches"] += mismatches
    readings["unsettled_share"] += share + share_b
    readings["unsettled_by_array"] += [float(np.mean(share)),
                                       float(np.mean(share_b))]
    readings["first_batch_difference"] = diff
    ok, refused = verdict(readings, spec["forced"]["min_decisive"])
    # on the line: the share, not a number a position
    return dict(readings, ok=ok, refused=refused, unsettled_share=[
        float(np.mean(readings["unsettled_share"]))])


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
    try:
        compiled = int(metrics.COMPILE_MISSES.value)
        loaded = int(metrics.COMPILE_PERSISTENT_HITS.value)
        check = check_engine(server, engine, model, cell, rng,
                             arch["vocab"], readings)
        trace = (cell["trace_at_s"], cell["trace_window_s"]) \
            if ctx["trace"] else None
        with Sampler(engine, 0.02 if trace else 0.5) as sampler:
            out = offer(server, engine, mix, seconds, seed, arch["vocab"],
                        trace)
        cache = engine.cache
        cache_note = {"max_slots": cache.max_slots, "entries": cache.n_rows,
                      "bytes": cache.bytes_by_kind(),
                      "dtype": str(cache.dtype)}
    finally:
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
            # what metrics/decode_hbm_pct.py reads: the layers' weights
            # once a loop step and the head once, and the live K and V
            # rows of every entry to the position
            "param_bytes": loop_bytes.step_weight_bytes(arch, itemsize),
            "live_kv_rows": loop_bytes.live_row_equivalents(stretch, arch),
            # what metrics/decode_attn_roofline_pct.py holds the ragged
            # kernel's calls of a step to
            "attn_bytes": loop_bytes.attn_bytes(stretch, arch, itemsize),
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
            kv_row_bytes=loop_bytes.row_bytes(arch, itemsize),
            max_slots=engine.max_slots),
        "trace": red,
        "breakdown": breakdown,
        "notes": {
            "check": check, "build_s": build_s, "check_s": check_s,
            "warmup_s": warmup_s, "programs_warmed": engine.warmed,
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
            # what a seed changes of the window's work (PERF.md, PR 35):
            # the admissions, the steps, the slots past the kernel's
            # first block
            "window": {
                "prefills": int(delta["prefill_n"]),
                "iterations": int(delta["iterations"]),
                "slots_live_mean": float(np.mean(
                    [len(p) for p in positions])) if positions else None,
                "slots_past_512_mean": float(np.mean(
                    [(np.asarray(p) >= 512).sum() for p in positions]))
                if positions else None,
                "position_mean": float(np.mean(np.concatenate(positions)))
                if positions and sum(map(len, positions)) else None,
            },
            "cache": cache_note,
            "traced": traced,
            "errors": seen["errors"],
        },
    }
