"""Job kind ``serve_state``: a zoo LM whose slots hold more than K/V rows
(the Phi-4-mini-flash family: recurrent state, window rows, one shared
full-attention cache) behind the continuous-batching engine, built as
tools/serve.py builds it (``DecodeModel.from_block`` ->
``GenerationEngine`` -> ``GenerationServer``) and driven in-process by
open-loop traffic.  The load, the clients' view and the counters are
``serve_generate``'s, unchanged, so ``chipbench/sweep.py`` works on a
cell of this kind as it is.

Cell file keys: as ``serve_generate``; ``check`` holds ``prompt_lengths``
(prefill: logits and everything it installs), ``forced`` (the decode
program driven directly: ``prompts`` the compared slots' prompt lengths,
``copies`` slots of each, ``steps`` forced tokens, ``min_decisive``),
``decode_prompt`` and ``new_tokens`` (greedy decoding through the
engine), ``batch_prompts`` (the range of the other prompts' lengths in
a full batch).
Configuration keys: ``zoo``, ``zoo_args``, ``zoo_kwargs`` (with the
serving dtype), ``serve_dtype``, the ``arch`` group.
"""
import importlib
import threading
import time

import numpy as np

from chipbench.harness import hybrid_bytes, trace_reduce, traffic
from chipbench.harness import reference_phi4flash as reference
from chipbench.jobs.serve_generate import counters, offer, summarize

__all__ = ["build_server", "check_against_reference", "counters", "offer",
           "summarize", "run"]

# System (bfloat16 weights and activations; float32 recurrence, softmax
# and logits) against the float32 reference at precision "highest" ON
# THE SAME bfloat16-rounded weights, max |a - b| over max |b|.  Each
# limit lies between two readings (my chip runs, PR 28; PERF.md has the
# runs): the largest the system gave over 19 runs of other seeds, and
# what the reference itself gives with every layer's matrices rounded to
# float8_e4m3, the nearest precision below the configuration's, which
# ``chipbench/precision.py`` puts through ``verdict`` below and which
# comes out as not correct by each limit:
#  - last-token prefill logits (32 layers of bfloat16 matmuls):
#    system 0.034-0.058, float8 0.49 and 0.55;
#  - the recurrence's state, the worst of the nine Mamba layers (float32
#    arithmetic on inputs that came through bfloat16 matmuls), as a
#    prefill installs it and as the decode program leaves it after the
#    forced steps: system 0.037-0.084, float8 0.46-0.76.  A state KEPT
#    in bfloat16 or float16 adds a rounding a step on top of the first
#    reading;
#  - K and V as the caches hold them, the last window layer's ring
#    (RING_TOL) and the full-attention layer's rows (ROWS_TOL), after a
#    prefill and after the forced steps: system 0.026-0.044, float8
#    0.38-0.48; a row in the wrong column, or a window off by one
#    position, reads near 1.
# The decode program hands back tokens, not logits: a token is held to
# the reference's argmax wherever the reference's two largest logits
# differ by more than DECISIVE x max |logit|, twice the largest logit
# error the system has read.  With random weights that is one position
# in twenty, so the forced steps compare hundreds of positions (19-32
# were decisive in each run; the float8 control moved 9 of 15) and the
# run is refused if fewer than the cell's ``min_decisive`` are.
LOGIT_TOL = 0.16
STATE_TOL = 0.17
RING_TOL = 0.12
ROWS_TOL = 0.12
DECISIVE = 0.11
LIMITS = {"prefill_logit_err": LOGIT_TOL, "state_err": STATE_TOL,
          "ring_err": RING_TOL, "rows_err": ROWS_TOL}


def build_model(config, seed):
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    module, fn = config["zoo"].split(":")
    mx.random.seed(seed % (2 ** 31))
    net = getattr(importlib.import_module(module), fn)(
        *config["zoo_args"], **config["zoo_kwargs"])
    # inference: no gradient buffer beside each weight
    net.collect_params().setattr("grad_req", "null")
    net.initialize()
    return serving.DecodeModel.from_block(net)


def build_server(config, cell, seed):
    from mxnet_tpu import serving
    model = build_model(config, seed)
    engine = serving.GenerationEngine(model, **cell["engine"])
    t = time.perf_counter()
    server = serving.GenerationServer(engine=engine, warmup=True).start()
    return server, engine, model, time.perf_counter() - t


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def reference_pass(model, tokens, head_rows):
    """The reference over ``tokens`` at their own length, a layer at a
    time so that one layer's float32 copy is all that is added to the
    device: (logits of the rows ``head_rows``, what each layer holds).
    ``model`` needs ``params`` and ``cfg`` only."""
    import jax
    import jax.numpy as jnp
    cfg = model.cfg
    step = jax.jit(
        lambda p, x, depth, carry, kind: reference.layer(
            reference.to_float32(p), x, kind, depth, cfg, carry),
        static_argnames="kind")
    x = jnp.asarray(model.params["embed"][jnp.asarray(tokens)], jnp.float32)
    carry, held = {}, []
    for depth, (kind, p) in enumerate(zip(cfg["kinds"],
                                          model.params["layers"])):
        x, carry, h = step(p, x, jnp.float32(depth), carry, kind=kind)
        held.append(h)
    with jax.default_matmul_precision("highest"):
        x = reference.layer_norm(
            x[jnp.asarray(head_rows)],
            jnp.asarray(model.params["lnf_g"], jnp.float32),
            jnp.asarray(model.params["lnf_b"], jnp.float32),
            cfg["layer_norm_eps"])
    # an eighth of the vocabulary at a time, each fetched before the
    # next is asked for: dispatched together the float32 copies of the
    # eight slices are 2 GB at once
    embed = model.params["embed"]
    size = -(-embed.shape[0] // 8)
    return np.concatenate(
        [np.asarray(reference.lm_logits(embed[i:i + size], x))
         for i in range(0, embed.shape[0], size)], axis=-1), held


# What a sequence of n tokens leaves in a cache, in one form for the
# system's slot and for the reference: every Mamba layer's state, the
# last window layer's K and V of the positions a query at n would still
# see, the full-attention layer's K and V of all n.

def _layers_of(cfg):
    kinds = cfg["kinds"]
    return ([i for i, k in enumerate(kinds) if k == "mamba"],
            max(i for i, k in enumerate(kinds) if k == "window"),
            kinds.index("full"))


def reference_holding(held, n, cfg):
    mamba, last_window, full = _layers_of(cfg)
    seen = np.arange(max(0, n - cfg["window"]), n)
    return {"ssm": [np.asarray(held[i]) for i in mamba],
            "ring": [np.asarray(a)[seen] for a in held[last_window]],
            "rows": [np.asarray(a)[:n] for a in held[full]]}


def slot_holding(ssm, rings, rows, n, cfg):
    """``ssm``: a (d_inner, d_state) array a Mamba layer; ``rings``: the
    last window layer's K and V (kv, window), position p in column
    p % window; ``rows``: the full layer's K and V (positions, kv)."""
    W = cfg["window"]
    seen = np.arange(max(0, n - W), n)
    return {"ssm": [np.asarray(a) for a in ssm],
            "ring": [np.asarray(a, np.float32).T[seen % W] for a in rings],
            "rows": [np.asarray(a, np.float32).reshape(len(a), -1)[:n]
                     for a in rows]}


def holding_errs(got, want):
    return {"state_err": [max(_rel(a, b) for a, b in zip(got["ssm"],
                                                         want["ssm"]))],
            "ring_err": [_rel(a, b) for a, b in zip(got["ring"],
                                                    want["ring"])],
            "rows_err": [_rel(a, b) for a, b in zip(got["rows"],
                                                    want["rows"])]}


def decisive_rows(want):
    """Rows of the reference's logits whose argmax a rounding cannot
    move."""
    top2 = np.sort(want, axis=-1)[:, -2:]
    return (top2[:, 1] - top2[:, 0]) > DECISIVE * np.abs(want).max()


def verdict(readings, min_decisive):
    """(correct, the names of what refuses): every error under its limit,
    every decisive token the reference's, and enough of them."""
    refused = [name for name, limit in LIMITS.items()
               if max(readings[name]) > limit]
    if readings["decisive_mismatches"]:
        refused.append("decisive_mismatches")
    if readings["decisive_positions"] < min_decisive:
        refused.append("decisive_positions")
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


def drive_decode_program(model, engine, prompts, forced):
    """Admit ``prompts``, one a slot, into a cache of the engine's shape
    and run ``model.step`` (the program the window times, at the
    engine's slots and first bucket) over ``forced`` (steps, slots)
    whatever it answers.  Returns its answers (steps, slots) and the
    cache."""
    cache = model.make_cache(engine.max_slots, engine.grid)
    for slot, p in enumerate(prompts):
        bucket = min(b for b in engine.prompt_buckets if b >= len(p))
        _, ks, vs, state = model.prefill(p, bucket)
        cache.write_prompt(slot, ks, vs, len(p), state=state)
    answers = []
    for toks in forced:
        answers.append(model.step(cache, toks, cache.positions))
        cache.positions += 1
    return np.stack(answers), cache


def check_against_reference(server, engine, model, cell, rng, vocab):
    """What the timed path produces, against the reference's full
    forward pass: prefill (logits and everything it installs); the
    decode program driven directly with every slot live, forced tokens
    in, across the window's edge (its tokens wherever the reference is
    decisive, and what it leaves in the compared slots); greedy decoding
    through the engine, alone and in a full batch."""
    spec, cfg = cell["check"], model.cfg
    # the last window layer's place among the window layers
    n_window = cfg["kinds"].count("window") - 1
    readings = {name: [] for name in LIMITS}

    def add(errs):
        for name, values in errs.items():
            readings[name] += values

    for n in spec["prompt_lengths"]:
        p = rng.integers(0, vocab, n, dtype=np.int32)
        bucket = min(b for b in engine.prompt_buckets if b >= n)
        got, ks, vs, state = model.prefill(p, bucket)
        want, held = reference_pass(model, p, [n - 1])
        add({"prefill_logit_err": [_rel(got, want[0])]})
        add(holding_errs(
            slot_holding(state["ssm"],
                         (state["wk"][n_window], state["wv"][n_window]),
                         (ks[0], vs[0]), n, cfg),
            reference_holding(held, n, cfg)))

    prompts, forced, compared = forced_plan(spec, engine.max_slots, rng,
                                            vocab)
    answers, cache = drive_decode_program(model, engine, prompts, forced)
    left = {slot: slot_holding(
        [a[slot] for a in cache.state["ssm"]],
        (cache.state["wk"][n_window][slot],
         cache.state["wv"][n_window][slot]),
        (np.asarray(cache.k(0)[slot]).T, np.asarray(cache.v(0)[slot]).T),
        len(prompts[slot]) + len(forced), cfg) for slot in compared}
    del cache           # a second cache is 1.9 GB beside the reference
    decisive_n = mismatches = 0
    for slot in compared:
        t0, n = len(prompts[slot]), len(prompts[slot]) + len(forced)
        want, held = reference_pass(
            model, np.concatenate([prompts[slot], forced[:, slot]]),
            np.arange(t0, n))
        decisive = decisive_rows(want)
        decisive_n += int(decisive.sum())
        mismatches += int((answers[:, slot] != want.argmax(-1))[decisive]
                          .sum())
        add(holding_errs(left[slot], reference_holding(held, n, cfg)))

    # through the engine: admission, the scheduler, the streams
    n_new = spec["new_tokens"]
    prompt = rng.integers(0, vocab, spec["decode_prompt"], dtype=np.int32)

    def greedy(p):
        return server.generate(p, max_new_tokens=n_new, method="greedy")

    alone = greedy(prompt).result()
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(alone))
    want, _ = reference_pass(model, np.concatenate([prompt, alone[:-1]]), at)
    decisive = decisive_rows(want)
    decisive_n += int(decisive.sum())
    mismatches += int((np.asarray(alone) != want.argmax(-1))[decisive].sum())
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
    readings.update(decisive_positions=decisive_n,
                    decisive_mismatches=mismatches,
                    first_batch_difference=diff)
    ok, refused = verdict(readings, spec["forced"]["min_decisive"])
    return dict(readings, ok=ok, refused=refused)


class Sampler:
    """Every ``period`` seconds, with the host's clock: the live slots'
    positions (what ``hybrid_bytes`` turns into the bytes a decode step
    must touch), the program's gauge of the bytes its slot cache has
    allocated, summed over kinds, and the rows' bucket."""

    KINDS = ("rows", "window", "state")     # of mxnet_gen_cache_bytes

    def __init__(self, engine, period):
        self._engine, self._period, self.samples = engine, period, []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="chipbench-sampler")

    def _run(self):
        from mxnet_tpu import metrics
        while not self._stop.wait(self._period):
            cache = self._engine.cache
            pos = cache.positions
            self.samples.append((
                time.perf_counter(), pos[pos >= 0].copy(),
                sum(metrics.value("mxnet_gen_cache_bytes", kind=k)
                    for k in self.KINDS), int(cache.bucket)))

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        return False

    def between(self, lo, hi):
        """(positions, allocated bytes, bucket) of each sample in
        [lo, hi], as three lists."""
        kept = [sample[1:] for sample in self.samples
                if lo <= sample[0] <= hi]
        return tuple(map(list, zip(*kept))) if kept else ([], [], [])


def soak(server, engine, mix, rng, vocab):
    """Bring the rows to the bucket a deployment of this traffic runs
    at: the one the mix's longest request needs.  Under the cell's mix
    one request in eight passes the second-last bucket's end, and with
    the slots always full some slot always holds one, so from its second
    minute on a deployment is at the top bucket for good; a window that
    opens 40 s after a cold start would catch the climb, at a moment the
    arrival order decides (PERF.md, PR 28: 1345-1686 tokens/s over five
    seeds).  One request of the mix's longest prompt decodes alone,
    through the engine, until the rows have grown; it stays resident
    while the ramp fills the slots (an emptied engine starts again from
    the first bucket).  Returns the function that cancels it."""
    from mxnet_tpu.serving.kv_cache import round_up_bucket
    n = int(mix["prompt"]["max"])
    target = round_up_bucket(n + int(mix["output"]["max"]), engine.grid)
    if target == engine.grid[0]:
        return lambda: None
    pilot = server.generate(
        rng.integers(0, vocab, n, dtype=np.int32), method="greedy",
        max_new_tokens=min(engine.max_tokens_cap, target - n))
    while engine.cache.bucket < target:
        if pilot.finish_reason is not None:
            raise RuntimeError(
                f"the soak request ended ({pilot.finish_reason}) after "
                f"{len(pilot.tokens)} tokens with the rows at "
                f"{engine.cache.bucket}, not {target}")
        time.sleep(0.05)
    return pilot.cancel


def run(ctx):
    cell, config, seed = ctx["cell"], ctx["config"], ctx["seed"]
    arch, mix, seconds = config["arch"], cell["traffic"], ctx["seconds"]
    rng = np.random.default_rng(seed)
    server, engine, model, warmup_s = build_server(config, cell, seed)
    end_soak = None
    try:
        from mxnet_tpu import metrics
        compiled = int(metrics.COMPILE_MISSES.value)
        loaded = int(metrics.COMPILE_PERSISTENT_HITS.value)
        check = check_against_reference(server, engine, model, cell, rng,
                                        arch["vocab"])
        trace = (cell["trace_at_s"], cell["trace_window_s"]) \
            if ctx["trace"] else None
        t_soak = time.perf_counter()
        cancel = soak(server, engine, mix, rng, arch["vocab"])
        soak_s = time.perf_counter() - t_soak
        # the slots are full two thirds into the ramp; the pilot's is
        # then given back to the traffic
        end_soak = threading.Timer(0.75 * float(mix["ramp_s"]), cancel)
        end_soak.start()
        # the traced stretch is read every 20 ms; an untraced run only
        # notes the buckets its window saw
        with Sampler(engine, 0.02 if trace else 0.5) as sampler:
            out = offer(server, engine, mix, seconds, seed, arch["vocab"],
                        trace)
        # host-side numbers only: the engine's thread owns the buffers
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
    breakdown, live_rows, cache_bytes = None, None, None
    import jax
    itemsize = jax.numpy.dtype(config["serve_dtype"]).itemsize
    if red is not None:
        breakdown = {
            "device_ops": trace_reduce.top(red["ops"]),
            "idle_gaps": trace_reduce.gaps_by_phase(
                red["gaps"], out["events"], red["offset_ns"]),
        }
        # offer() opens the trace at_s into the window, as here
        at_s = min(trace[0], max(0.0, seconds - trace[1]) / 2)
        lo = out["t0"] + at_s
        positions, allocated, _ = sampler.between(
            lo, lo + min(trace[1], seconds))
        live_rows = hybrid_bytes.live_row_equivalents(positions, arch,
                                                      itemsize)
        cache_bytes = float(np.mean(allocated)) if allocated else None
    return {
        "correct": check["ok"],
        "attempted": seen["attempted"],
        "failed": seen["failed"],
        "compiled_in_window": int(delta["compiles"]),
        "end_to_end": {
            "setup_s": out["t0"] - ctx["t_proc"],
            "serve_tokens_per_s": seen["tokens_per_s"],
        },
        "readings": {
            "warmup_s": warmup_s,
            "delta": delta,
            "lag_ms": seen["lag_ms"],
            # what metrics/decode_hbm_pct.py reads: every weight once
            # plus, in units of one attention layer's K and V row, what
            # the live slots make a step touch (harness/hybrid_bytes.py)
            "param_bytes": sum(
                a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(model.params)),
            "live_kv_rows": live_rows,
            "kv_row_bytes": hybrid_bytes.row_bytes(arch, itemsize),
            "cache_bytes": cache_bytes,
            "max_slots": engine.max_slots,
        },
        "trace": red,
        "breakdown": breakdown,
        "notes": {
            "check": check, "warmup_s": warmup_s, "soak_s": soak_s,
            "programs_warmed": engine.warmed,
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
            "live_row_equivalents": live_rows,
            "errors": seen["errors"],
        },
    }
