"""Benchmark harness: one module per paper table/figure (see DESIGN.md §8).

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--only tableN]
"""
import argparse
import sys
import time

from benchmarks import (adapt_bench, audit_bench, engine_bench,
                        fig6_filter_tradeoff, fig8_groupby, fig9_guarantees,
                        index_bench, join_bench, kernels_bench,
                        pipeline_bench, quant_bench, serve_bench,
                        shard_bench, stream_bench, table2_factcheck,
                        table3_biodex, table5_join_plans, table6_7_ranking,
                        trace_bench)

MODULES = {
    "table2": table2_factcheck,
    "table3": table3_biodex,
    "table5": table5_join_plans,
    "table6_7": table6_7_ranking,
    "fig6": fig6_filter_tradeoff,
    "fig8": fig8_groupby,
    "fig9": fig9_guarantees,
    "pipeline": pipeline_bench,
    "serve": serve_bench,
    "index": index_bench,
    "quant": quant_bench,
    "stream": stream_bench,
    "shard": shard_bench,
    "engine": engine_bench,
    "kernels": kernels_bench,
    "trace": trace_bench,
    "adapt": adapt_bench,
    "audit": audit_bench,
    "join": join_bench,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated module keys")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    keys = args.only.split(",") if args.only else list(MODULES)
    print("name,us_per_call,derived")
    t0 = time.monotonic()
    for k in keys:
        try:
            MODULES[k].run()
        except Exception as e:  # pragma: no cover
            print(f"{k}/ERROR,nan,{type(e).__name__}:{e}", flush=True)
            raise
    print(f"# total {time.monotonic()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
