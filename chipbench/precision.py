"""The second reading behind a ``serve_state`` cell's limits — the
builder's tool, like sweep.py; it prints a table and no contract line.

    python chipbench/precision.py --workload phi4_mini_flash.serve_reason --seed 1

The job's ``check_against_reference`` holds the system (the
configuration's dtype) to the float32 reference on the same weights.
Each of its limits has to lie between what the system reads there and
what THE REFERENCE ITSELF reads once it is computed in the nearest
precision below the configuration's: here with every layer's matrices
rounded to float8_e4m3 (bfloat16's neighbour below; the embedding stays
as it is).  This takes that second reading in the check's own
quantities, on the check's own sequences (its prefill lengths, and its
forced prompts with their forced tokens), and puts it through the job's
``verdict``, which has to refuse it.  No engine is built and nothing is
warmed up: one model, two reference passes a sequence.  Exit code 1 if
the verdict lets the rounded reference through.
"""
import argparse
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np                              # noqa: E402

from chipbench import run                       # noqa: E402


def control_readings(job, model, low, spec, rng, vocab):
    """``check_against_reference``'s readings with the reference on the
    weights ``low`` standing in for the system."""
    cfg = model.cfg
    readings = {name: [] for name in job.LIMITS}
    f = spec["forced"]
    sequences = [(n, n - 1) for n in spec["prompt_lengths"]] \
        + [(n + f["steps"], n) for n in f["prompts"]]
    decisive_n = mismatches = 0
    for n, first_row in sequences:
        p = rng.integers(0, vocab, n, dtype=np.int32)
        rows = np.arange(first_row, n)
        want, held = job.reference_pass(model, p, rows)
        got, held_low = job.reference_pass(low, p, rows)
        if len(rows) == 1:
            readings["prefill_logit_err"].append(job._rel(got, want))
        else:
            decisive = job.decisive_rows(want)
            decisive_n += int(decisive.sum())
            mismatches += int((got.argmax(-1) != want.argmax(-1))[decisive]
                              .sum())
        for name, values in job.holding_errs(
                job.reference_holding(held_low, n, cfg),
                job.reference_holding(held, n, cfg)).items():
            readings[name] += values
    readings.update(decisive_positions=decisive_n,
                    decisive_mismatches=mismatches)
    return readings


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    run.place_compile_cache()
    found = run.resolve(run.ROOT, args.workload)
    run.require_tpu(found["chips"])
    import jax
    import jax.numpy as jnp
    job, cell, config = found["job"], found["cell"], found["config"]
    model = job.build_model(config, args.seed)

    # rounded EAGERLY, a matrix a program, and kept as float8: inside
    # one program XLA may drop a narrowing and widening pair of converts
    # (on the v5e it did, and this read 3e-6)
    low = types.SimpleNamespace(cfg=model.cfg, params=dict(
        model.params, layers=jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float8_e4m3fn) if a.ndim >= 2 else a,
            model.params["layers"])))

    readings = control_readings(
        job, model, low, cell["check"], np.random.default_rng(args.seed),
        config["arch"]["vocab"])
    ok, refused = job.verdict(readings, cell["check"]["forced"]["min_decisive"])
    print(json.dumps({
        "control": "weights rounded to float8_e4m3 against the same "
                   f"reference on the {config['serve_dtype']} weights",
        "limits": job.LIMITS, "readings": readings,
        "correct": ok, "refused_by": refused}), flush=True)
    sys.exit(1 if ok else 0)


if __name__ == "__main__":
    main()
