"""The second readings behind a ``serve_delta`` cell's limits — a
measurement tool beside precision.py; it prints one JSON line and no
contract line.

    python chipbench/delta_control.py --workload olmo_hybrid_7b.serve_chat --seed 1

Two controls on the check's own sequences, each put through the job's
limits, which have to refuse it: ``precision.py``'s (every layer's
matrices rounded to float8_e4m3, through the job's ``verdict``) and the
reference with its matrix state rounded to bfloat16 after every token
(``state_control``: the first linear layer's limit has to catch it).
One model is built for both; no engine.  Exit code 1 if either control
passes.
"""
import argparse
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np                              # noqa: E402

from chipbench import precision, run            # noqa: E402


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
    spec, vocab = cell["check"], config["arch"]["vocab"]
    model = job.build_model(config, args.seed)
    # rounded eagerly, a matrix a program, and kept as float8 (as
    # precision.py rounds them)
    low = types.SimpleNamespace(cfg=model.cfg, params=dict(
        model.params, layers=jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float8_e4m3fn) if a.ndim >= 2 else a,
            model.params["layers"])))
    f8 = precision.control_readings(job, model, low, spec,
                                    np.random.default_rng(args.seed), vocab)
    f8_ok, f8_refused = job.verdict(f8, spec["forced"]["min_decisive"])
    state, over = job.state_control(model, spec,
                                    np.random.default_rng(args.seed), vocab,
                                    jnp.bfloat16)
    print(json.dumps({
        "limits": job.LIMITS,
        "float8": {"readings": f8, "correct": f8_ok, "refused_by": f8_refused},
        "state_bfloat16": {"readings": state, "over": over}}), flush=True)
    sys.exit(1 if f8_ok or not over else 0)


if __name__ == "__main__":
    main()
