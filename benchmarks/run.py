"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--full`` runs the paper's full
label-ratio grid and worker counts; default is the quick profile.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: quality,label,ablation,"
                         "parallel,kernels,train,partition,online,roofline")
    args = ap.parse_args()
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None
    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()

    sections = []
    if only is None or "quality" in only:
        from benchmarks import bench_batch_quality
        sections.append(("batch_quality(fig1c,2a,2b)",
                         lambda: bench_batch_quality.run(quick)))
    if only is None or "label" in only:
        from benchmarks import bench_label_ratio
        sections.append(("label_ratio(fig3a)",
                         lambda: bench_label_ratio.run(quick)))
    if only is None or "ablation" in only:
        from benchmarks import bench_batching_ablation
        sections.append(("batching_ablation(§2)",
                         lambda: bench_batching_ablation.run(quick)))
    if only is None or "parallel" in only:
        from benchmarks import bench_parallel
        sections.append(("parallel(fig3b,3c)",
                         lambda: bench_parallel.run(quick)))
    if only is None or "kernels" in only:
        from benchmarks import bench_kernels
        # Timings also land in BENCH_kernels.json (machine-readable: fwd and
        # fwd+bwd for ref vs fused) so the perf trajectory survives across PRs.
        sections.append(("kernels", lambda: bench_kernels.run(
            quick, json_path="BENCH_kernels.json")))
    if only is None or "train" in only:
        from benchmarks import bench_train
        # Training throughput lands in BENCH_train.json (python-loop vs the
        # scan-compiled engine, per strategy) — the loop-speed trajectory.
        sections.append(("train(engine)", lambda: bench_train.run(
            quick, json_path="BENCH_train.json")))
    if only is None or "partition" in only:
        from benchmarks import bench_partition
        # Partition wall-clock lands in BENCH_partition.json (seed loop vs
        # vectorized at matched seeds, cut ratios, per-epoch replan cost
        # from-scratch AND with hierarchy reuse); the replan summary also
        # lands in BENCH_partition_replan.json.  Both B=2048 and B=512 run
        # in smoke mode, and the ratio gates raise on regression (the
        # section then fails the job).
        sections.append(("partition(loop_vs_vec)", lambda: bench_partition.run(
            quick, json_path="BENCH_partition.json",
            replan_json_path="BENCH_partition_replan.json")))
    if only is None or "online" in only:
        from benchmarks import bench_online
        # Refresh latency + insert/evict throughput land in
        # BENCH_online.json — the cost trajectory of keeping the graph
        # synced to the live model.
        sections.append(("online(refresh+ingest)", lambda: bench_online.run(
            quick, json_path="BENCH_online.json")))
    if only is None or "roofline" in only:
        from benchmarks import bench_roofline

        def roofline_rows():
            recs = bench_roofline.load()
            return bench_roofline.csv_rows(
                [r for r in recs if r["mesh"] == "single_pod_16x16"
                 and r["strategy"] == "fsdp_tp"])
        sections.append(("roofline(dry-run)", roofline_rows))

    print("name,us_per_call,derived")
    ok = True
    for name, fn in sections:
        print(f"# --- {name} ---")
        try:
            for row in fn():
                print(row)
        except Exception:  # noqa: BLE001
            ok = False
            print(f"# SECTION FAILED: {name}", file=sys.stderr)
            traceback.print_exc()
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
