"""Benchmark entry point: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [names...]

Prints ``name,value,derived`` CSV rows.  The fed benchmarks are scaled-down
(CPU) versions of the paper's experiments on synthetic structured data; the
``roofline`` benchmark reads the dry-run artifacts if present.

Whenever the ``kernels`` bench runs, its rows are also written to
``benchmarks/BENCH_stc.json`` (and the ``wire`` bench's to
``benchmarks/BENCH_wire.json``) so the perf trajectories are tracked across
PRs (compare the committed file against a fresh run).
"""

from __future__ import annotations

import datetime
import json
import os
import platform
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_STC_PATH = os.path.join(_HERE, "BENCH_stc.json")
BENCH_WIRE_PATH = os.path.join(_HERE, "BENCH_wire.json")
BENCH_ASYNC_PATH = os.path.join(_HERE, "BENCH_async.json")
BENCH_CHUNKED_PATH = os.path.join(_HERE, "BENCH_chunked.json")
BENCH_INGEST_PATH = os.path.join(_HERE, "BENCH_ingest.json")
BENCH_EVENTS_PATH = os.path.join(_HERE, "BENCH_events.json")
BENCH_FAULTS_PATH = os.path.join(_HERE, "BENCH_faults.json")
BENCH_ROBUST_PATH = os.path.join(_HERE, "BENCH_robust.json")
BENCH_ADAPTIVE_PATH = os.path.join(_HERE, "BENCH_adaptive.json")


def _write_bench(path: str, rows, unit: str = "us") -> None:
    """Persist bench rows for cross-PR tracking (``scripts/check_bench.py``
    gates the slow CI lane on them).  Timing files keep the historical
    ``us`` value key; non-timing files (unit != "us") use ``value``."""
    key = "us" if unit == "us" else "value"
    payload = {
        "generated": datetime.datetime.now(datetime.timezone.utc)
        .isoformat(timespec="seconds"),
        "host": {"machine": platform.machine(),
                 "python": platform.python_version()},
        "unit": unit,
        "rows": [{"name": name,
                  key: round(float(val), 1 if unit == "us" else 4),
                  "note": derived}
                 for name, val, derived in rows],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")


def write_bench_stc(rows) -> None:
    _write_bench(BENCH_STC_PATH, rows)


def write_bench_wire(rows) -> None:
    _write_bench(BENCH_WIRE_PATH, rows)


def write_bench_async(rows) -> None:
    _write_bench(BENCH_ASYNC_PATH, rows, unit="mixed")


def write_bench_chunked(rows) -> None:
    _write_bench(BENCH_CHUNKED_PATH, rows)


def write_bench_ingest(rows) -> None:
    _write_bench(BENCH_INGEST_PATH, rows, unit="mixed")


def write_bench_events(rows) -> None:
    _write_bench(BENCH_EVENTS_PATH, rows, unit="mixed")


def write_bench_faults(rows) -> None:
    _write_bench(BENCH_FAULTS_PATH, rows, unit="mixed")


def write_bench_robust(rows) -> None:
    _write_bench(BENCH_ROBUST_PATH, rows, unit="mixed")


def write_bench_adaptive(rows) -> None:
    _write_bench(BENCH_ADAPTIVE_PATH, rows, unit="mixed")


def main() -> None:
    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    quick = "--quick" in sys.argv

    from benchmarks import kernel_bench, paper_claims
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    rows = []
    which = args or ["golomb", "wire", "kernels", "chunked", "ingest",
                     "events", "faults", "robust", "adaptive", "async",
                     "fig3", "fig5", "fig2", "table4", "fig8", "roofline"]
    if quick:
        which = args or ["golomb", "wire", "kernels", "chunked", "ingest",
                         "events", "faults", "robust", "adaptive", "fig3"]

    for name in which:
        print(f"# === {name} ===", flush=True)
        if name == "kernels":
            krows = kernel_bench.run(verbose=False)
            write_bench_stc(krows)
            rows += krows
        elif name == "wire":
            from benchmarks import wire_bench
            wrows = wire_bench.run(verbose=False)
            write_bench_wire(wrows)
            rows += wrows
        elif name == "chunked":
            from benchmarks import chunked_bench
            crows = chunked_bench.run(verbose=False)
            write_bench_chunked(crows)
            rows += crows
        elif name == "ingest":
            from benchmarks import ingest_bench
            irows = ingest_bench.run(verbose=False, smoke=quick)
            if not quick:    # quick = smoke scale; keep the tracked file
                write_bench_ingest(irows)    # at the fleet operating point
            rows += irows
        elif name == "events":
            from benchmarks import events_bench
            erows = events_bench.run(verbose=False, smoke=quick)
            if not quick:    # quick = smoke scale; keep the tracked file
                write_bench_events(erows)    # at the full scenario sweep
            rows += erows
        elif name == "faults":
            from benchmarks import faults_bench
            frows = faults_bench.run(verbose=False, smoke=quick)
            if not quick:    # quick = smoke scale; keep the tracked file
                write_bench_faults(frows)    # at the full chaos sweep
            rows += frows
        elif name == "robust":
            from benchmarks import robust_bench
            brows = robust_bench.run(verbose=False, smoke=quick)
            if not quick:    # quick = smoke scale; keep the tracked file
                write_bench_robust(brows)    # at the full rule x attack sweep
            rows += brows
        elif name == "adaptive":
            from benchmarks import adaptive_bench
            adrows = adaptive_bench.run(verbose=False, smoke=quick)
            if not quick:    # quick = smoke scale; keep the tracked file
                write_bench_adaptive(adrows)  # full accuracy-per-bit sweep
            rows += adrows
        elif name == "async":
            from benchmarks import async_bench
            arows = async_bench.run(verbose=False)
            write_bench_async(arows)
            rows += arows
        elif name == "roofline":
            from benchmarks import roofline
            recs = roofline.load_records()
            if not recs:
                print("# (no dry-run artifacts; skipping roofline rows)")
                continue
            for r in recs:
                a = roofline.analyze(r)
                rows.append((f"roofline/{a['arch']}/{a['shape']}/{a['mesh']}",
                             max(a["t_compute_s"], a["t_memory_s"],
                                 a["t_collective_s"]),
                             f"dominant={a['dominant']}"))
        else:
            rows += paper_claims.BENCHES[name](verbose=False)

    print("name,value,derived")
    for name, val, derived in rows:
        print(f"{name},{val},{derived}")


if __name__ == "__main__":
    main()
