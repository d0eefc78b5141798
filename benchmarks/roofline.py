"""Roofline analysis from the dry-run artifacts (deliverable g).

Reads artifacts/dryrun/*.json (written by repro.launch.dryrun) and derives,
per (arch x shape x mesh):

    compute term    = HLO_FLOPs_per_chip / peak_FLOP/s
    memory term     = HLO_bytes_per_chip / HBM_bw
    collective term = wire_bytes_per_chip / link_bw
    ingest term     = measured_server_ingest_bytes_per_round / NIC_bw

The ingest term comes from the dry-run's WireLedger measurement (one
sampled client update encoded through the codec's real wire format, scaled
to the cohort); it is reported alongside the per-step terms but kept out of
``dominant`` because the buffered server overlaps ingest with compute.

cost_analysis() on the SPMD-partitioned executable reports PER-CHIP figures
(verified against analytic parameter/argument sizes in EXPERIMENTS.md
§Dry-run).  Collective result bytes are converted to wire bytes with the
standard ring factors; the group size n is approximated by the mesh axis the
collective most plausibly runs over (model=16) -- noted as approximate.

Chip peaks come from ``PEAKS``, keyed by ``jax.Device.device_kind``; a kind
that is not in the table raises.  The dry-run's production meshes describe
v5e pods, so their records are read against ``DRYRUN_DEVICE_KIND``.
"""

from __future__ import annotations

import glob
import json
import os
import sys

from repro.configs import INPUT_SHAPES, get_config

# Published per-chip peaks, keyed by jax's device_kind.  Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, HBM at 819 GB/s, 1,600 Gbit/s
# of chip-to-chip interconnect over 4 links).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}
DRYRUN_DEVICE_KIND = "TPU v5 lite"   # the pods the dry-run meshes describe


def chip_peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; unknown raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to benchmarks/roofline.py PEAKS "
                       f"(known: {sorted(PEAKS)})") from None


SERVER_NIC_BW = 12.5e9  # 100 Gb/s front-end NIC: client uploads enter here
DEFAULT_GROUP = 16  # model-axis size; collectives are predominantly TP

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")


def wire_bytes(collectives: dict, n: int = DEFAULT_GROUP) -> float:
    """Convert result bytes to per-chip wire bytes (ring algorithms)."""
    total = 0.0
    for kind, rec in collectives.items():
        b = rec["bytes"]
        if kind == "all-reduce":
            total += 2.0 * b * (n - 1) / n
        elif kind == "all-gather":
            total += b * (n - 1) / n
        elif kind == "reduce-scatter":
            total += b * (n - 1)          # result is the scattered shard
        elif kind == "all-to-all":
            total += b * (n - 1) / n
        elif kind == "collective-permute":
            total += b
    return total


def model_flops(rec: dict) -> float:
    """MODEL_FLOPS (global): 6·N_active·D train, 2·N_active·D forward-only."""
    cfg = get_config(rec["arch"], rec["shape"])
    shape = INPUT_SHAPES[rec["shape"]]
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def analyze(rec: dict) -> dict:
    peaks = chip_peaks(DRYRUN_DEVICE_KIND)
    chips = 512 if rec["mesh"] == "2x16x16" else 256
    t_compute = rec["flops"] / peaks["bf16_flops"]
    t_memory = rec["bytes_accessed"] / peaks["hbm_bw"]
    t_coll = wire_bytes(rec.get("collectives", {})) / peaks["link_bw"]
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec)
    hlo_global = rec["flops"] * chips
    # measured server ingest (WireLedger figure recorded by the dry-run):
    # wall time for one round's client uploads to cross the front-end NIC.
    # Reported alongside the per-step terms, not folded into `dominant` --
    # ingest overlaps training steps in the buffered server.
    si = rec.get("server_ingest")
    t_ingest = (si["bytes_up_round"] / SERVER_NIC_BW) if si else 0.0
    return {
        **rec,
        "chips": chips,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "t_ingest_s": t_ingest,
        "ingest_bytes_round": si["bytes_up_round"] if si else 0.0,
        "dominant": dominant,
        "model_flops": mf,
        "useful_ratio": mf / hlo_global if hlo_global else 0.0,
    }


_SUGGEST = {
    "compute": ("reduce recompute (remat policy) / ensure matmul dims are "
                "128-aligned so padded-head waste stops burning MXU cycles"),
    "memory": ("cut activation traffic: chunk the LM head, fuse the STC "
               "residual chain (Pallas kernel), bf16 the gradient tree"),
    "collective": ("overlap the message psum with backward, shrink gathered "
                   "tensors (reduce-scatter the server stage), or move expert "
                   "weights to an all_to_all expert-parallel layout"),
}


def suggestion(a: dict) -> str:
    return _SUGGEST[a["dominant"]]


def load_records(variant: str | None = None):
    recs = []
    for path in sorted(glob.glob(os.path.join(os.path.abspath(ART), "*.json"))):
        with open(path) as f:
            r = json.load(f)
        if variant is None and r.get("variant"):
            continue
        if variant is not None and r.get("variant") != variant:
            continue
        recs.append(r)
    return recs


def table(recs) -> str:
    lines = ["| arch | shape | mesh | compute s | memory s | collective s | "
             "ingest s | dominant | MODEL/HLO |",
             "|---|---|---|---|---|---|---|---|---|"]
    for r in recs:
        a = analyze(r)
        ingest = (f"{a['t_ingest_s']:.3e}" if a["t_ingest_s"] else "--")
        lines.append(
            f"| {a['arch']} | {a['shape']} | {a['mesh']} "
            f"| {a['t_compute_s']:.3e} | {a['t_memory_s']:.3e} "
            f"| {a['t_collective_s']:.3e} | {ingest} | **{a['dominant']}** "
            f"| {a['useful_ratio']:.3f} |")
    return "\n".join(lines)


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    variant = args[0] if args else None
    recs = load_records(variant)
    if not recs:
        print("no dry-run artifacts found -- run repro.launch.dryrun first")
        return
    print(table(recs))
    print()
    for r in recs:
        a = analyze(r)
        print(f"{a['arch']} x {a['shape']} x {a['mesh']}: dominant="
              f"{a['dominant']} -> {suggestion(a)}")
        if a["t_ingest_s"]:
            print(f"  server ingest (measured): "
                  f"{a['ingest_bytes_round'] / 2**20:.2f} MiB/round = "
                  f"{a['t_ingest_s']:.3e} s on the front-end NIC")
    if "--write" in sys.argv:
        out = os.path.join(os.path.dirname(__file__), "..", "artifacts",
                           "roofline_table.md")
        with open(os.path.abspath(out), "w") as f:
            f.write("# Roofline baseline table (single-pod 16x16 + "
                    "multi-pod 2x16x16)\n\n")
            f.write(table(recs))
            f.write("\n\n## Per-pair bottleneck notes\n\n")
            for r in recs:
                a = analyze(r)
                f.write(f"* **{a['arch']} × {a['shape']} × {a['mesh']}** — "
                        f"dominant {a['dominant']}: {suggestion(a)}\n")
        print(f"\nwrote {out}")


if __name__ == "__main__":
    main()
