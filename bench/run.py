#!/usr/bin/env python3
"""Chip benchmark of the federated mesh round.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: checks that the attached devices are TPUs, as many as the cell
asks for (else it exits non-zero and prints no result); builds the cell's
configuration and traffic from their files; makes the weights on the device
from the seed; compiles the program's jitted round
(``repro.launch.train.make_train_step``) and drives it through its first
rounds on distinct batches (set-up); then runs rounds back to back, reading
each round's loss on the host, until the first round that ends after
``--seconds``.  With ``--trace 1`` that window is traced and the result
carries the cell's per-layer metrics instead of its end-to-end ones.

After the window and the memory reading, the program's state is freed and
the plain reference runs the same first rounds; the comparison
(``bench/check.py``) decides ``correct``.  The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SETUP_ROUNDS = 3       # rounds the reference follows; all in set-up
GiB = float(1 << 30)
# JAX refuses a cache entry larger than ``jax_compilation_cache_max_size``
# (which JAX_COMPILATION_CACHE_MAX_SIZE can set): the round of a full model
# is an entry of 100-300 MB, so a smaller cap recompiles it in every run
CACHE_MIN_SIZE = 2 * (1 << 30)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_gate(chips: int) -> dict:
    """The attached devices as JAX reports them; exits unless they are at
    least ``chips`` TPUs."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        sys.exit(f"bench: JAX found no TPU (platform {info['platform']!r}); "
                 f"nothing was run")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} TPUs, JAX found {len(devs)}")
    return info


def use_compile_cache(root: str) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set, else
    the fixed ``<checkout>/.jax_cache``; a size cap under
    ``CACHE_MIN_SIZE`` is raised to it for this process."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    cap = jax.config.jax_compilation_cache_max_size
    if 0 <= cap < CACHE_MIN_SIZE:
        jax.config.update("jax_compilation_cache_max_size", CACHE_MIN_SIZE)
    return path


def log(**kw) -> None:
    print(json.dumps(kw, default=float), file=sys.stderr, flush=True)


def run(args, root: str = ROOT) -> dict:
    import jax
    import numpy as np

    from bench import check, reference
    from bench.spec import Cell, load_module

    cell = Cell(root, args.workload)
    device = device_gate(cell.chips)
    cache = use_compile_cache(root)
    model_ref = load_module(cell.model_ref_path)
    proto_ref = load_module(cell.protocol_ref_path)
    from bench.program import Program, seed_key

    marks = {"start_to_devices": time.perf_counter() - T_START}
    with jax.profiler.TraceAnnotation("bench.setup"):
        t0 = time.perf_counter()
        prog = Program(cell, args.seed, model_ref)
        state = prog.make_state(prog.key)
        jax.block_until_ready(state)
        marks["batches_and_weights"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        step = prog.compile(state)
        marks["lower_and_compile"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        nb = len(prog.batches)
        readings = {"losses": []}
        for r in range(SETUP_ROUNDS):
            state, metrics = step(state, prog.batches[r % nb])
            readings["losses"].append(float(metrics["loss"]))
            if r == 0:
                readings["update_norms"] = np.asarray(
                    prog.first_update_norms(state, prog.key))
        readings["change_norms"] = np.asarray(
            prog.change_norms(state["params"], prog.key))
        marks["setup_rounds_and_readings"] = time.perf_counter() - t0
    setup_s = time.perf_counter() - T_START
    log(cell=cell.name, setup_s=setup_s, setup_parts=marks, cache=cache,
        setup_losses=readings["losses"])

    trace_dir = os.path.join(root, ".bench_trace", f"{cell.name}-{args.seed}")
    if args.trace:
        jax.profiler.start_trace(trace_dir)
    rounds, failed, losses = 0, 0, []
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            state, metrics = step(state, prog.batches[
                (SETUP_ROUNDS + rounds) % nb])
        with jax.profiler.TraceAnnotation("bench.fetch"):
            loss = float(metrics["loss"])
        rounds += 1
        losses.append(loss)
        failed += not math.isfinite(loss)
        if time.perf_counter() - t0 >= args.seconds:
            break
    window_s = time.perf_counter() - t0
    if args.trace:
        jax.profiler.stop_trace()
        from bench.trace import OP_NAMES, hlo_op_names
        with open(os.path.join(trace_dir, OP_NAMES), "w") as fh:
            json.dump(hlo_op_names(step.as_text()), fh)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in prog.devices())
    log(window_s=window_s, rounds=rounds, first_losses=losses[:3],
        peak_bytes_in_use=peak)
    host_batches = prog.host_batches
    leaf_names = [jax.tree_util.keystr(k) for k, _ in
                  jax.tree_util.tree_flatten_with_path(state["params"])[0]]
    del state, metrics, step, prog
    gc.collect()

    t0 = time.perf_counter()
    ref = reference.readings(model_ref, proto_ref, cell.model, cell.traffic,
                             seed_key(args.seed),
                             [host_batches[r % nb]
                              for r in range(SETUP_ROUNDS)], SETUP_ROUNDS)
    numbers = check.gaps(readings, ref)
    correct, checks = check.judge(numbers, cell.limits)
    correct = correct and failed == 0 and all(
        math.isfinite(x) for x in readings["losses"])
    log(reference_s=time.perf_counter() - t0, ref_losses=ref["losses"],
        prog_losses=readings["losses"],
        worst_leaves=check.worst_leaves(readings, ref, leaf_names))

    result = {"correct": bool(correct), "attempted": rounds, "failed": failed}
    if args.trace:
        from bench.trace import reduce_trace
        red = reduce_trace(trace_dir, cell, device, rounds)
        shutil.rmtree(trace_dir)
        log(layer_s=red.layer_s, busy_s=red.busy_s, traced_window_s=red.window_s)
        result["metrics"] = red.metrics
        device = dict(device, busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = red.breakdown
    else:
        values = {"round_s": window_s / rounds, "peak_hbm_gib": peak / GiB,
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = dict(device, memory_peak_bytes=peak)
    result["checks"] = checks
    return result


def main(argv=None) -> None:
    args = parse(argv)
    result = run(args)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
