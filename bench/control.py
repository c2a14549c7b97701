"""Readings that a cell's correctness limit is set from, on the chip, at
the cell's own size and load, over many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10 \
        [--out control.jsonl]

For each seed: fresh weights, the cell's lead-in and a short window of its
traffic through the engine, then the plain reference over the same sample
of finished requests a benchmark run checks. Each line gives the widest
gap of a served token below the reference's best (the program's reading:
the limit's lower side) and, at the same positions, the widest gap of the
token that the float8 control of the reference puts first (the control's
reading: the limit's upper side).
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import generator, run  # noqa: E402


def free_pools(eng):
    import jax.numpy as jnp
    kept = [(a.shape, a.dtype) for a in eng._pools()]
    eng._set_pools(tuple(None for _ in kept))
    gc.collect()

    def restore(dev):
        import jax
        eng._set_pools(tuple(jax.device_put(jnp.zeros(sh, dt), dev)
                             for sh, dt in kept))
    return restore


def one_seed(c, s, seed: int, seconds: float) -> dict:
    import jax
    eng = s.eng
    eng.params = s.weights = None
    gc.collect()
    s.weights = c.ref.make_weights(c.sizes, seed)
    eng.params = jax.device_put(s.weights, s.dev)
    reqs = generator.generate(c.mix, seed, c.sizes["vocab"],
                              c.mix["lead_in_s"] + seconds + run.DRAIN_S)
    d = run.drive(eng, c.mix, reqs, time.perf_counter(), seconds, s.clock,
                  limits=c.limits)
    cl = d.client
    lim = c.limits
    sample = run.pick_sample([r for r in cl.recs if r.done >= cl.t0], seed,
                             lim["sample_tokens"], lim["sample_requests"])
    sample = [dataclasses.replace(r, req=None) for r in sample]
    for r in list(eng.sched.waiting) + list(eng.sched.running):
        eng.cancel(r.req_id)
    eng.drain()
    eng.reset()
    restore = free_pools(eng)
    t = time.perf_counter()
    gaps = run.served_gaps(c, s.weights, sample, control=True)
    ref_s = time.perf_counter() - t
    restore(s.dev)
    return {"workload": c.name, "seed": seed,
            "requests": len(sample),
            "tokens": int(sum(len(g) for g, _ in gaps)),
            "program_gap_max": max(float(g.max()) for g, _ in gaps),
            "control_gap_max": max(float(x.max()) for _, x in gaps),
            "program_gap_per_request": [float(g.max()) for g, _ in gaps],
            "reference_s": ref_s,
            "window_compiles": d.compiles_window[0]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    seeds = [int(x) for x in args.seeds.split(",")]
    c = run.load_cell(args.workload)
    try:
        s = run.set_up(c, seeds[0])
    except run.Refused as e:
        run.log(f"refused: {e}")
        return 3
    out = open(args.out, "a") if args.out else None

    def emit(line):
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()

    for seed in seeds:
        emit(one_seed(c, s, seed, args.seconds))
    return 0


if __name__ == "__main__":
    sys.exit(main())
