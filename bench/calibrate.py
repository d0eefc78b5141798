#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's, the control's and the
planted faults'.

    python3 bench/calibrate.py --workload <cell> --seeds 11 12 13 [--program]
        [--no-controls]

For each seed, the plain reference's first rounds (float32, ``highest``)
against the same reference put in the program's place (a) in the nearest
lower precision, fp8 matmul operands (the control), (b) trained on half of
each client's rows, the mean taken over the rest, and (c), with more than
one client, with the exchange left out.  With ``--program``, also the
program's own first rounds, driven as ``bench/run.py`` drives them in
set-up, through one compiled round for all the seeds: the lower readings of
a dozen seeds in one process.  Prints one JSON line per seed with the three
numbers of ``bench/check.py`` for each.  The benchmark's runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

ROUNDS = 3


def variants(clients: int) -> dict:
    out = {"control_fp8": {"precision": "fp8"},
           "fault_half_batch": {"fault": "half_batch"}}
    if clients > 1:
        out["fault_no_exchange"] = {"fault": "no_exchange"}
    return out


def program_readings(cell, seed: int, model_ref, step=None):
    """The program's readings of its first ``ROUNDS`` rounds, as
    ``bench/run.py`` takes them in set-up; ``step`` is a round compiled for
    another seed of the cell, or None.  Returns ``(readings, leaf names,
    step)``; the program's state is freed."""
    import jax
    import numpy as np

    from bench.program import Program

    prog = Program(cell, seed, model_ref)
    state = prog.make_state(prog.key)
    if step is None:
        step = prog.compile(state)
    nb = len(prog.batches)
    out = {"losses": []}
    for r in range(ROUNDS):
        state, metrics = step(state, prog.batches[r % nb])
        out["losses"].append(float(metrics["loss"]))
        if r == 0:
            out["update_norms"] = np.asarray(
                prog.first_update_norms(state, prog.key))
    out["change_norms"] = np.asarray(
        prog.change_norms(state["params"], prog.key))
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(state["params"])[0]]
    del state, metrics, prog
    gc.collect()
    return out, names, step


def calibrate(cell, seed: int, program: bool = False, controls: bool = True,
              step=None) -> tuple[dict, object]:
    """One seed's line, and the compiled round for the next seed."""
    import numpy as np

    from bench import check, reference
    from bench.program import seed_key
    from bench.spec import load_module
    from bench.tokens import client_batches

    t, m = cell.traffic, cell.model
    model_ref = load_module(cell.model_ref_path)
    proto_ref = load_module(cell.protocol_ref_path)
    out = {"seed": seed}
    if program:
        prog, names, step = program_readings(cell, seed, model_ref, step)
    batches = client_batches(seed, t["clients"], t["batches"],
                             t["batch_per_client"],
                             t["seq_len"], m["vocab_size"])
    key = seed_key(seed)
    base = reference.readings(model_ref, proto_ref, m, t, key, batches, ROUNDS)
    out["ref_losses"] = base["losses"]
    if program:
        out["program"] = check.gaps(prog, base)
        out["prog_losses"] = prog["losses"]
        out["change_norm"] = [
            float(np.sqrt(np.sum(np.square(r["change_norms"],
                                           dtype=np.float64))))
            for r in (prog, base)]
        out["worst_leaves"] = check.worst_leaves(prog, base, names)
    for name, kw in (variants(t["clients"]) if controls else {}).items():
        got = reference.readings(model_ref, proto_ref, m, t, key, batches,
                                 ROUNDS, **kw)
        out[name] = check.gaps(got, base)
    return out, step


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true",
                    help="also read the program's own first rounds")
    ap.add_argument("--no-controls", dest="controls", action="store_false",
                    help="leave out the control and the faults")
    args = ap.parse_args(argv)
    from bench.spec import Cell
    cell = Cell(ROOT, args.workload)
    if args.program:
        from bench.run import use_compile_cache
        use_compile_cache(ROOT)
    step = None
    for seed in args.seeds:
        line, step = calibrate(cell, seed, args.program, args.controls, step)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
