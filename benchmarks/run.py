"""Benchmark harness: one entry per paper table/figure + the dry-run
roofline. Prints ``name,us_per_call,derived`` CSV (assignment format).

--skip mod1,mod2 excludes entries (CI runs the throughput benchmarks as
dedicated steps and skips them here to avoid paying for them twice).

After the entries run, every BENCH_*.json in the repo root is checked
against the key schema below; drift (missing/extra/unknown keys) makes
the harness exit nonzero so a benchmark refactor cannot silently change
what the headline artifacts report."""
import argparse
import json
import os
import sys

# Key schema for each headline artifact. A benchmark that wants to add or
# drop a metric must update this table in the same change — that is the
# point: the diff shows the contract moving.
BENCH_SCHEMAS = {
    "BENCH_serve.json": frozenset({
        "tokens_per_s", "seed_loop_tokens_per_s", "speedup_vs_seed_loop",
        "host_syncs_per_token", "traces",
        "paged_slots", "paged_tokens_per_s", "paged_vs_fused_tokens_ratio",
        "paged_kv_bytes_ratio", "paged_peak_live_tokens_frac",
        "paged_bit_identical", "paged_admission_stalls", "paged_traces",
    }),
    "BENCH_train.json": frozenset({
        "fused_round_ms", "seed_loop_round_ms", "speedup_vs_seed_loop",
        "fused_tokens_per_s", "seed_loop_tokens_per_s",
        "host_syncs_per_step", "seed_host_syncs_per_step", "n_pods",
        "inner_steps", "outer_sync_compress", "outer_wire_predicted_bytes",
        "outer_wire_measured_bytes", "outer_wire_measured_over_predicted",
        "outer_wire_within_budget",
    }),
    "BENCH_coserve.json": frozenset({
        "coserve_tokens_per_s", "coserve_tokens_per_engine_active_s",
        "coserve_p50_block_ms", "serve_only_tokens_per_s",
        "serve_only_tokens_per_engine_active_s", "serve_only_p50_block_ms",
        "throughput_ratio_vs_serve_only",
        "active_throughput_ratio_vs_serve_only", "engine_active_fraction",
        "rounds", "param_swaps", "published_round", "traces_before_swaps",
        "traces_after_swaps", "n_pods", "inner_steps",
    }),
    "BENCH_fleet.json": frozenset({
        "replicas", "slots_per_replica", "plane_tokens_per_s",
        "plane_p50_step_ms", "plane_throughput_ratio_vs_single",
        "single_tokens_per_s", "single_p50_step_ms", "chaos_schedule",
        "grid_chaos_tokens_per_s", "grid_failover_events",
        "grid_failover_p50_stall_ms", "grid_failover_p99_stall_ms",
        "grid_pointer_flips", "grid_full_migrations",
        "grid_rebalanced_slots", "full_drain_chaos_tokens_per_s",
        "full_drain_failover_events", "full_drain_failover_p50_stall_ms",
        "full_drain_failover_p99_stall_ms", "full_drain_migrated_slots",
        "failover_p50_impact_vs_full_drain", "grid_replicated_rows",
        "grid_full_rows_equiv", "replication_savings_ratio",
        "masked_pod_ticks", "zero_drops_under_chaos", "traces",
        "mixed_archs", "mixed_chaos_tokens_per_s", "mixed_p50_step_ms",
        "mixed_pointer_flips", "mixed_full_migrations",
        "mixed_replicated_rows", "mixed_full_rows_equiv",
        "mixed_arch_occupancy", "mixed_zero_drops_under_chaos",
    }),
}

REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")


def check_bench_schemas() -> list[str]:
    """Compare every repo-root BENCH_*.json against BENCH_SCHEMAS."""
    problems = []
    import glob
    for path in sorted(glob.glob(os.path.join(REPO_ROOT, "BENCH_*.json"))):
        name = os.path.basename(path)
        schema = BENCH_SCHEMAS.get(name)
        if schema is None:
            problems.append(f"{name}: no schema in benchmarks/run.py "
                            f"BENCH_SCHEMAS (new artifact? declare it)")
            continue
        try:
            keys = set(json.load(open(path)))
        except (json.JSONDecodeError, OSError) as e:
            problems.append(f"{name}: unreadable ({e})")
            continue
        missing = schema - keys
        extra = keys - schema
        if missing:
            problems.append(f"{name}: missing keys {sorted(missing)}")
        if extra:
            problems.append(f"{name}: undeclared keys {sorted(extra)}")
    return problems


def main() -> int:
    from benchmarks import (coserve, diloco_traffic, fig1_isl,
                            fig2_constellation, fig4_launch, fleet_serve,
                            j2_drift, radiation_table, roofline,
                            serve_throughput, table1_power,
                            train_throughput)
    mods = [fig1_isl, fig2_constellation, j2_drift, radiation_table,
            fig4_launch, table1_power, diloco_traffic, roofline,
            train_throughput, serve_throughput, coserve, fleet_serve]
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip", default="",
                    help="comma-separated module names to exclude")
    skip = {s.strip() for s in ap.parse_args().skip.split(",") if s.strip()}
    mods = [m for m in mods if m.__name__.rsplit(".", 1)[-1] not in skip]
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    for mod in mods:
        try:
            out, _ = mod.run()
            for name, us, derived in out:
                print(f'{name},{us:.1f},"{derived}"')
        except Exception as e:  # keep the harness running
            print(f'{mod.__name__},-1,"FAILED: {e!r}"')
    problems = check_bench_schemas()
    for p in problems:
        print(f"BENCH-SCHEMA-DRIFT: {p}", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
