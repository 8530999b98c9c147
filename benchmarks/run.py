"""One-shot human-readable benchmark report over the whole suite.

Everything here is registry-driven: one function per paper table/figure
(``paper_tables.ALL``), every ``@register_kernel`` entry's reference
timing + streamed-path smoke, the stream-analysis reports, the
compiled-nest gate (gemm/stencil ssr-vs-baseline agreement + Eq. (1)–(3)
model speedup), the fused-vs-unfused race, and — if dry-run records exist —
the roofline summary.  Adding a kernel to the registry adds it to this
report with zero edits.

``benchmarks/kernel_bench.py`` is the machine-readable twin (writes +
validates ``BENCH_kernels.json``); this entry point just prints the
``name,us_per_call,derived`` CSV for eyeballing and logs.
"""

from __future__ import annotations

import os


def main() -> None:
    from benchmarks import kernel_bench, paper_tables
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    rows = []
    for fn in paper_tables.ALL:
        rows.extend(fn())
    # kernel_bench rows are structured dicts (BENCH_kernels.json schema);
    # flatten them into the CSV triple this report prints.
    for row in (kernel_bench.bench_reference_paths()
                + kernel_bench.smoke_ssr_paths()
                + kernel_bench.bench_stream_reports()
                + kernel_bench.bench_nest_gate()
                + kernel_bench.bench_fused()):
        rows.append((f"{row['name']}/{row['variant']}", row["value"],
                     row["units"]))

    if os.path.exists("dryrun_results.json"):
        from benchmarks import roofline
        print("\n== roofline (from dry-run records) ==")
        rf = roofline.load("dryrun_results.json")
        print(roofline.table(rf, "pod16x16"))
        for r in rf:
            if r["mesh"] == "pod16x16":
                rows.append((f"roofline/{r['arch']}/{r['shape']}",
                             r["roofline_fraction"],
                             f"dominant={r['dominant']}"))

    print("\nname,us_per_call,derived")
    for name, val, derived in rows:
        print(f"{name},{val},{derived}")


if __name__ == "__main__":
    main()
