"""The plain order the engine's transcripts are held to: one request
alone in a cache, driven by ``DecodeModel.step`` synchronously (launch,
wait, read, then launch again from the host's token), with none of the
engine's scheduling.  Both served families go through it
(``model.make_cache`` / ``prefill`` / ``select`` / ``step``), greedy and
sampled.  Shared by test_generation, test_gen_sampling, test_phi4flash
and test_resilience; not a test file.
"""
import numpy as np

from mxnet_tpu import metrics
from mxnet_tpu.serving.kv_cache import round_up_bucket
from mxnet_tpu.serving.model import METHOD_CODES


def serial_transcript(model, engine, prompt, max_new_tokens, eos_token=None,
                      method="greedy", temperature=1.0, top_k=40, top_p=0.9,
                      seed=0):
    """(tokens, finish_reason) of one request decoded alone, serially,
    on a cache of ``engine``'s shape."""
    prompt = np.asarray(prompt, np.int32)
    cache = model.make_cache(engine.max_slots, engine.grid, prefix_slots=0)
    slot = cache.alloc()
    t0 = int(prompt.size)
    logits, ks, vs, *state = model.prefill(
        prompt, round_up_bucket(t0, engine.prompt_buckets))
    cache.write_prompt(slot, ks, vs, t0,
                       state=state[0] if state else None)
    top_k = min(int(top_k), int(model.vocab_size))
    lanes = [np.array(v) for v in model.greedy_sampling(engine.max_slots)]
    for lane, value in zip(lanes, (seed, t0 - 1, temperature, top_k, top_p,
                                   METHOD_CODES[method])):
        lane[slot] = value
    tok = model.select(logits, seed, 0, temperature, top_k, top_p,
                       METHOD_CODES[method])
    out, last = [tok], np.zeros((engine.max_slots,), np.int32)
    while True:
        if eos_token is not None and tok == int(eos_token):
            return out, "eos"
        if len(out) >= max_new_tokens \
                or int(cache.positions[slot]) >= engine.grid[-1]:
            return out, "length"
        cache.ensure_capacity(cache.needed_capacity())
        last[slot] = tok
        pos = np.maximum(cache.positions, 0).astype(np.int32)
        tok = int(model.step(cache, last, pos, lanes)[slot])
        cache.positions[slot] += 1
        out.append(tok)


def run_staggered(engine, requests, max_iters=400):
    """Submit ``requests`` — dicts of ``submit`` keywords plus ``at``,
    the quantum before which each arrives — and run the engine until it
    has nothing left; returns the streams in order."""
    streams = [None] * len(requests)
    for it in range(max_iters):
        for i, r in enumerate(requests):
            if r["at"] == it:
                kw = {k: v for k, v in r.items() if k not in ("at", "prompt")}
                streams[i] = engine.submit(r["prompt"], **kw)
        if not engine.run_iteration() and all(
                s is not None for s in streams):
            return streams
    raise AssertionError("the engine did not finish the requests")


class StepCounters:
    """Movement of the run-ahead loop's counters since construction."""

    REASONS = ("idle", "finish", "admit", "cancel", "spec")

    def __init__(self):
        self._at = self._read()

    @staticmethod
    def _read():
        out = {r: metrics.value("mxnet_gen_step_fallbacks_total", reason=r)
               for r in StepCounters.REASONS}
        out.update(
            ahead=metrics.value("mxnet_gen_steps_ahead_total"),
            discarded=metrics.value("mxnet_gen_discarded_tokens_total"),
            iterations=metrics.value("mxnet_gen_iterations_total"),
            decode_tokens=metrics.value("mxnet_gen_tokens_total",
                                        phase="decode"),
            prefill_tokens=metrics.value("mxnet_gen_tokens_total",
                                         phase="prefill"),
            sampled=sum(metrics.value("mxnet_gen_sampled_tokens_total",
                                      method=m) for m in METHOD_CODES))
        return out

    def moved(self):
        now = self._read()
        return {k: now[k] - self._at[k] for k in now}

    def fallbacks(self):
        moved = self.moved()
        return sum(moved[r] for r in self.REASONS)


def check_log(engine, streams):
    """The per-iteration slot log: a slot's tokens are emitted at most
    once a quantum, and the ``decoded`` entries count exactly the
    decode-step tokens the streams received (an admission's first token
    is prefill's; a discarded token is in no entry)."""
    log = list(engine.iteration_log)
    for entry in log:
        assert len(set(entry["decoded"])) == len(entry["decoded"]), entry
    assert sum(len(e["decoded"]) for e in log) \
        == sum(len(s.tokens) - 1 for s in streams)
