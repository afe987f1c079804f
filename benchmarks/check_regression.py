"""Benchmark-regression gate: fresh smoke-run results vs committed baseline.

Compares a fresh ``bench_device.py`` report (and optionally a fresh
``bench_multiquery.py`` report) against the committed ``BENCH_device.json``
baseline and exits non-zero on regression.  Two kinds of checks:

contract (exact, noise-free — these ARE the paper-level guarantees):
  * every ``identical`` flag in the fresh run is true (bit-identical to the
    numpy oracle / block engines)
  * the tape engine's sync counts: one host sync + one device dispatch per
    single query, one sync per query in a tape batch, one bundled sync per
    lockstep batch — compared *per query*, so a smoke run (8-query batch)
    checks against a full baseline (64-query batch)
  * ``host_fallbacks == 0`` on the numeric and dict-string workloads (the
    dictionary rewrite keeps mixed plans device-resident)
  * the sharded section (``bench_device.py --sharded``) keeps the
    collective one-sync contract on an 8-device mesh: bit-identical to the
    single-device run, one collective sync per query (one bundled sync per
    lockstep batch), zero retraces across appends, and the delta re-upload
    confined to the dirty shard
  * observability is free: with telemetry + tracing enabled the results
    stay bit-identical and the sync/dispatch counts unchanged (fresh run),
    and the measured overhead is <= 5% on the committed full-scale
    baseline (device and serving sections)
  * the drift workload's Q-Error feedback loop closes: realized
    selectivities correct the estimator (``qerror_reduction``), stale
    cached plans are evicted-and-replanned (``drift_evictions > 0``), the
    replanned order is no worse than the naive plan under truth
    statistics, and the batch stays ONE bundled host sync throughout

throughput (tolerance-gated — CI machines and smoke sizes differ from the
committed 1M-row baseline, so this is a coarse floor, not a tight bound):
  * fresh speedup >= ``--speedup-tolerance`` x baseline speedup for the
    single / strings / batch sections
  * fresh multiquery speedup >= ``--min-multiquery-speedup`` and its
    dedupe ratio >= 1 (sharing still pays)

    PYTHONPATH=src python benchmarks/bench_device.py --smoke --out fresh.json
    python benchmarks/check_regression.py \
        --fresh-device fresh.json --baseline-device BENCH_device.json
"""
from __future__ import annotations

import argparse
import json
import sys


class Gate:
    """Collects named pass/fail checks and renders a report."""

    def __init__(self):
        self.failures = []
        self.passes = []

    def check(self, name: str, ok: bool, detail: str = "") -> None:
        (self.passes if ok else self.failures).append((name, detail))

    def report(self) -> int:
        for name, detail in self.passes:
            print(f"  ok    {name}" + (f"  ({detail})" if detail else ""))
        for name, detail in self.failures:
            print(f"  FAIL  {name}" + (f"  ({detail})" if detail else ""))
        if self.failures:
            print(f"REGRESSION: {len(self.failures)} check(s) failed")
            return 1
        print(f"all {len(self.passes)} checks passed")
        return 0


def check_device(gate: Gate, fresh: dict, base: dict, tol: float) -> None:
    single, bsingle = fresh.get("single", {}), base.get("single", {})
    batch, bbatch = fresh.get("batch", {}), base.get("batch", {})

    # -- contract: bit-identical everywhere ----------------------------------
    for section in ("single", "batch", "strings", "differential"):
        sec = fresh.get(section)
        if sec is not None:
            gate.check(f"{section}.identical", bool(sec.get("identical")))

    # -- contract: the one-sync tape guarantees ------------------------------
    gate.check("single.tape_host_syncs_per_query",
               single.get("tape_host_syncs_per_query")
               == bsingle.get("tape_host_syncs_per_query"),
               f"fresh={single.get('tape_host_syncs_per_query')} "
               f"baseline={bsingle.get('tape_host_syncs_per_query')}")
    gate.check("single.tape_device_dispatches",
               single.get("tape_device_dispatches")
               == bsingle.get("tape_device_dispatches"),
               f"fresh={single.get('tape_device_dispatches')} "
               f"baseline={bsingle.get('tape_device_dispatches')}")
    gate.check("single.host_fallbacks == 0",
               single.get("host_fallbacks", -1) == 0,
               f"fresh={single.get('host_fallbacks')}")
    # the tape batch launches every query's program, then ONE bundled sync
    gate.check("batch.tape_host_syncs_per_batch == 1",
               batch.get("tape_host_syncs_per_batch") == 1,
               f"fresh={batch.get('tape_host_syncs_per_batch')}")
    gate.check("batch.tape_lockstep_host_syncs_per_batch",
               batch.get("tape_lockstep_host_syncs_per_batch")
               == bbatch.get("tape_lockstep_host_syncs_per_batch"),
               f"fresh={batch.get('tape_lockstep_host_syncs_per_batch')} "
               f"baseline={bbatch.get('tape_lockstep_host_syncs_per_batch')}")

    # -- contract: the dict-string workload stays device-resident ------------
    strings, bstrings = fresh.get("strings"), base.get("strings")
    gate.check("strings section present", strings is not None)
    if strings is not None:
        gate.check("strings.host_fallbacks == 0",
                   strings.get("host_fallbacks", -1) == 0,
                   f"fresh={strings.get('host_fallbacks')}")
        gate.check("strings.tape_host_syncs_per_query == 1",
                   strings.get("tape_host_syncs_per_query") == 1,
                   f"fresh={strings.get('tape_host_syncs_per_query')}")
        gate.check("strings.tape_device_dispatches == 1",
                   strings.get("tape_device_dispatches") == 1,
                   f"fresh={strings.get('tape_device_dispatches')}")

    # -- contract: fragmented string atoms stay inside the one program -------
    fragmented, bfragmented = fresh.get("fragmented"), base.get("fragmented")
    gate.check("fragmented section present", fragmented is not None)
    if fragmented is not None:
        gate.check("fragmented.host_fallbacks == 0",
                   fragmented.get("host_fallbacks", -1) == 0,
                   f"fresh={fragmented.get('host_fallbacks')}")
        gate.check("fragmented.tape_host_syncs_per_query == 1",
                   fragmented.get("tape_host_syncs_per_query") == 1,
                   f"fresh={fragmented.get('tape_host_syncs_per_query')}")
        gate.check("fragmented.tape_device_dispatches == 1",
                   fragmented.get("tape_device_dispatches") == 1,
                   f"fresh={fragmented.get('tape_device_dispatches')}")

    # -- contract: zone pruning reaches the compiled path --------------------
    selective, bselective = fresh.get("selective"), base.get("selective")
    gate.check("selective section present", selective is not None)
    if selective is not None:
        gate.check("selective.blocks_pruned > 0",
                   selective.get("blocks_pruned", 0) > 0,
                   f"fresh={selective.get('blocks_pruned')}")
        gate.check("selective.host_fallbacks == 0",
                   selective.get("host_fallbacks", -1) == 0,
                   f"fresh={selective.get('host_fallbacks')}")
        gate.check("selective.tape_host_syncs_per_query == 1",
                   selective.get("tape_host_syncs_per_query") == 1,
                   f"fresh={selective.get('tape_host_syncs_per_query')}")
        gate.check("selective: appends do not retrace",
                   selective.get("programs_compiled_on_append", -1) == 0,
                   f"fresh={selective.get('programs_compiled_on_append')}")
        # the "pruning pays" claim is asserted on the committed full-scale
        # baseline (smoke tables are too small for the CPU-visible win to
        # clear its fixed costs); the fresh run is still collapse-gated by
        # the tolerance floor below
        gate.check("selective.speedup > 1 in committed baseline",
                   (bselective or {}).get("speedup", 0.0) > 1.0,
                   f"baseline={(bselective or {}).get('speedup')}")

    # -- contract: sharded execution keeps the one-collective-sync path ------
    sharded = fresh.get("sharded")
    gate.check("sharded section present", sharded is not None,
               "run bench_device.py with --sharded")
    if sharded is not None:
        gate.check("sharded.identical", bool(sharded.get("identical")))
        gate.check("sharded.one_sync_per_query",
                   bool(sharded.get("one_sync_per_query")),
                   f"fresh={sharded.get('one_sync_per_query')}")
        gate.check("sharded.lockstep_syncs_per_batch == 1",
                   sharded.get("lockstep_syncs_per_batch") == 1,
                   f"fresh={sharded.get('lockstep_syncs_per_batch')}")
        gate.check("sharded: appends do not retrace",
                   sharded.get("programs_compiled_on_append", -1) == 0,
                   f"fresh={sharded.get('programs_compiled_on_append')}")
        gate.check("sharded: small append re-uploads one shard",
                   sharded.get("delta_upload_shards") == 1,
                   f"fresh={sharded.get('delta_upload_shards')}")
        gate.check("sharded.devices == 8",
                   sharded.get("devices") == 8,
                   f"fresh={sharded.get('devices')}")

    # -- contract: the Q-Error feedback loop closes under drift --------------
    drift = fresh.get("drift")
    gate.check("drift section present", drift is not None)
    if drift is not None:
        gate.check("drift.identical", bool(drift.get("identical")))
        gate.check("drift.drift_evictions > 0",
                   drift.get("drift_evictions", 0) > 0,
                   f"fresh={drift.get('drift_evictions')}")
        gate.check("drift.host_syncs_per_batch == 1",
                   drift.get("host_syncs_per_batch") == 1,
                   f"fresh={drift.get('host_syncs_per_batch')}")
        gate.check("drift.qerror_reduction >= 1.5",
                   drift.get("qerror_reduction", 0.0) >= 1.5,
                   f"fresh={drift.get('qerror_reduction')}")
        # the replanned (post-feedback) order must be at least as good as
        # the naive fresh plan when both are costed under truth statistics
        gate.check("drift: post-feedback plan no worse than naive",
                   drift.get("plan_cost_ratio_feedback", 99.0)
                   <= drift.get("plan_cost_ratio_naive", 0.0) + 1e-9,
                   f"feedback={drift.get('plan_cost_ratio_feedback')} "
                   f"naive={drift.get('plan_cost_ratio_naive')}")
        gate.check("drift: post-feedback plan near truth (<= 1.05x)",
                   drift.get("plan_cost_ratio_feedback", 99.0) <= 1.05,
                   f"fresh={drift.get('plan_cost_ratio_feedback')}")

    # -- contract: observability is free (zero perturbation, bounded cost) ---
    obs, bobs = fresh.get("obs"), base.get("obs")
    gate.check("obs section present", obs is not None)
    if obs is not None:
        gate.check("obs.identical (telemetry/trace on == off)",
                   bool(obs.get("identical")))
        gate.check("obs sync/dispatch counts unchanged",
                   bool(obs.get("contracts_equal")),
                   f"syncs {obs.get('host_syncs_off')}->"
                   f"{obs.get('host_syncs_on')}, dispatches "
                   f"{obs.get('dispatches_off')}->"
                   f"{obs.get('dispatches_on')}")
        # the <=5% ceiling is asserted on the committed full-scale baseline
        # (smoke batches are small enough that a few ms of gauge publishing
        # reads as a large percentage); the fresh run still gates identity
        # and the sync contract exactly
        gate.check("obs.overhead <= 5% in committed baseline",
                   (bobs or {}).get("overhead_pct", 99.0) <= 5.0,
                   f"baseline={(bobs or {}).get('overhead_pct')}%")

    # -- throughput floors ----------------------------------------------------
    for name, sec, bsec in (("single", single, bsingle),
                            ("batch", batch, bbatch),
                            ("strings", strings, bstrings),
                            ("fragmented", fragmented, bfragmented),
                            ("selective", selective, bselective)):
        if not sec or not bsec:
            continue
        floor = tol * bsec.get("speedup", 0.0)
        gate.check(f"{name}.speedup >= {tol:g} x baseline",
                   sec.get("speedup", 0.0) >= floor,
                   f"fresh={sec.get('speedup')} baseline={bsec.get('speedup')}"
                   f" floor={floor:.2f}")


def check_stream(gate: Gate, fresh: dict, base: dict, tol: float,
                 min_speedup: float, min_warm_speedup: float) -> None:
    """Streaming-ingest gates: exact contracts (bit-identicality, one
    bundled sync per drained batch, every cached tape rebound) plus
    tolerance-gated floors on the delta-reuse ratio and the re-upload
    fraction (scale-free ratios, so a smoke run checks against the
    committed 1M-row baseline) and an *absolute* floor on the host-engine
    steady-state speedup (the baseline's 1M-row figure grows with table
    size, so a fraction of it would be unreachable for a smoke table)."""
    host = fresh.get("host", {})
    rebind = fresh.get("rebind", {})
    for name, sec in (("stream", fresh), ("stream.host", host)):
        gate.check(f"{name}.identical", bool(sec.get("identical")))
    gate.check("stream.host_syncs_per_batch == 1",
               fresh.get("host_syncs_per_batch") == 1,
               f"fresh={fresh.get('host_syncs_per_batch')}")
    gate.check("stream.rebind all tapes rebound",
               rebind.get("tape_cache_hits", -1) == rebind.get("queries"),
               f"hits={rebind.get('tape_cache_hits')} "
               f"queries={rebind.get('queries')}")
    floor = tol * base.get("delta_reuse_ratio", 0.0)
    gate.check(f"stream.delta_reuse_ratio >= {tol:g} x baseline",
               fresh.get("delta_reuse_ratio", 0.0) >= floor,
               f"fresh={fresh.get('delta_reuse_ratio')} floor={floor:.3f}")
    ceil = base.get("reupload_fraction", 1.0) / max(tol, 1e-9)
    gate.check(f"stream.reupload_fraction <= baseline / {tol:g}",
               fresh.get("reupload_fraction", 1.0) <= ceil,
               f"fresh={fresh.get('reupload_fraction')} ceiling={ceil:.3f}")
    gate.check(f"stream.host.speedup >= {min_speedup:g}",
               host.get("speedup", 0.0) >= min_speedup,
               f"fresh={host.get('speedup')}")
    sel = fresh.get("selective")
    gate.check("stream.selective section present", sel is not None)
    if sel is not None:
        gate.check("stream.selective.identical", bool(sel.get("identical")))
        gate.check("stream.selective.blocks_pruned > 0",
                   sel.get("blocks_pruned", 0) > 0,
                   f"fresh={sel.get('blocks_pruned')}")
        gate.check("stream.selective.host_fallbacks == 0",
                   sel.get("host_fallbacks", -1) == 0,
                   f"fresh={sel.get('host_fallbacks')}")
        gate.check("stream.selective.host_syncs_per_batch == 1",
                   sel.get("host_syncs_per_batch") == 1,
                   f"fresh={sel.get('host_syncs_per_batch')}")

    # -- contract: serving observability is free ------------------------------
    ob, bob = fresh.get("obs"), base.get("obs")
    gate.check("stream.obs section present", ob is not None)
    if ob is not None:
        gate.check("stream.obs.identical", bool(ob.get("identical")))
        gate.check("stream.obs syncs/drain unchanged",
                   ob.get("host_syncs_per_drain_off")
                   == ob.get("host_syncs_per_drain_on"),
                   f"off={ob.get('host_syncs_per_drain_off')} "
                   f"on={ob.get('host_syncs_per_drain_on')}")
        gate.check("stream.obs latency histogram sampled",
                   ob.get("latency_samples", 0) > 0,
                   f"fresh={ob.get('latency_samples')}")
        gate.check("stream.obs drain spans recorded",
                   ob.get("drain_spans", 0) > 0,
                   f"fresh={ob.get('drain_spans')}")
        gate.check("stream.obs.overhead <= 5% in committed baseline",
                   (bob or {}).get("overhead_pct", 99.0) <= 5.0,
                   f"baseline={(bob or {}).get('overhead_pct')}%")

    # -- contract: durability (WAL overhead, crash recovery) ------------------
    dur, bdur = fresh.get("durable"), base.get("durable")
    gate.check("stream.durable section present", dur is not None,
               "run bench_stream.py with --durable")
    if dur is not None:
        gate.check("stream.durable.identical (WAL on == off)",
                   bool(dur.get("identical")))
        gate.check("stream.durable.recovery_identical",
                   bool(dur.get("recovery_identical")))
        gate.check("stream.durable: drain left no uncommitted records",
                   dur.get("wal_uncommitted", -1) == 0,
                   f"fresh={dur.get('wal_uncommitted')}")
        gate.check("stream.durable: recovery replayed a snapshot + tail",
                   dur.get("snapshot_seq", 0) > 0
                   and dur.get("replayed_records", 0) > 0,
                   f"snapshot_seq={dur.get('snapshot_seq')} "
                   f"replayed={dur.get('replayed_records')}")
        gate.check("stream.durable: clean WAL (no torn records)",
                   dur.get("truncated_records", -1) == 0,
                   f"fresh={dur.get('truncated_records')}")
        # the overhead ceiling is asserted on the committed full-scale
        # baseline (smoke drains are short enough that a single fsync
        # reads as a large percentage); the fresh run still gates the
        # bit-identicality contracts exactly
        gate.check("stream.durable.overhead <= 10% in committed baseline",
                   (bdur or {}).get("overhead_pct", 99.0) <= 10.0,
                   f"baseline={(bdur or {}).get('overhead_pct')}%")
        # recovery time: an absolute collapse detector, scaled off the
        # committed baseline with a floor that absorbs cold-start noise
        ceil_ms = max(5.0 * (bdur or {}).get("recovery_ms", 0.0), 250.0)
        gate.check("stream.durable.recovery_ms bounded",
                   dur.get("recovery_ms", 1e9) <= ceil_ms,
                   f"fresh={dur.get('recovery_ms')}ms ceiling={ceil_ms:.0f}ms")

    # -- contract: serving SLOs (fault degradation, tombstones, restarts) ----
    slo = fresh.get("slo")
    gate.check("stream.slo section present", slo is not None,
               "run bench_stream.py with --slo")
    if slo is not None:
        flt = slo.get("faults", {})
        gate.check("stream.slo.faults.degraded_batches > 0",
                   flt.get("degraded_batches", 0) > 0,
                   f"fresh={flt.get('degraded_batches')}")
        gate.check("stream.slo.faults.lost_futures == 0",
                   flt.get("lost_futures", -1) == 0,
                   f"fresh={flt.get('lost_futures')}")
        gate.check("stream.slo.faults.identical (degraded == device)",
                   bool(flt.get("identical")))
        gate.check("stream.slo.sync_per_drain_with_tombstones == 1",
                   slo.get("sync_per_drain_with_tombstones") == 1,
                   f"fresh={slo.get('sync_per_drain_with_tombstones')}")
        gate.check("stream.slo.tombstones degraded the batch? no",
                   slo.get("degraded_with_tombstones", -1) == 0,
                   f"fresh={slo.get('degraded_with_tombstones')}")
        gate.check("stream.slo.tombstones_respected",
                   bool(slo.get("tombstones_respected")))
        wr = slo.get("warm_restart", {})
        gate.check(f"stream.slo.warm_speedup >= {min_warm_speedup:g}",
                   wr.get("warm_speedup", 0.0) >= min_warm_speedup,
                   f"cold={wr.get('cold_first_drain_ms')}ms "
                   f"warm={wr.get('warm_first_drain_ms')}ms "
                   f"speedup={wr.get('warm_speedup')}")
        gate.check("stream.slo.warm tape_cache_hits > 0",
                   wr.get("tape_cache_hits_warm", 0) > 0,
                   f"fresh={wr.get('tape_cache_hits_warm')}")
        gate.check("stream.slo.warm_restart.identical",
                   bool(wr.get("identical")))
        lat = slo.get("latency", {})
        gate.check("stream.slo.latency sampled",
                   lat.get("samples", 0) > 0 and lat.get("p99_ms", 0.0) > 0.0,
                   f"samples={lat.get('samples')} p99={lat.get('p99_ms')}")


def check_multiquery(gate: Gate, fresh: dict, min_speedup: float) -> None:
    gate.check("multiquery.identical", bool(fresh.get("identical")))
    gate.check("multiquery.dedupe_ratio >= 1",
               fresh.get("dedupe_ratio", 0.0) >= 1.0,
               f"fresh={fresh.get('dedupe_ratio')}")
    gate.check(f"multiquery.speedup >= {min_speedup:g}",
               fresh.get("speedup", 0.0) >= min_speedup,
               f"fresh={fresh.get('speedup')}")
    db = fresh.get("dict_buckets")
    gate.check("multiquery.dict_buckets present", db is not None)
    if db is not None:
        # tight dict-atom buckets must not degrade plan quality (that is
        # their whole point) nor collapse the hit rate
        ratio = db.get("records_ratio_tight_vs_coarse", 99.0)
        gate.check("dict_buckets: tight plans no worse (ratio <= 1.05)",
                   ratio <= 1.05, f"fresh={ratio}")
        tight = db.get("tight", {}).get("plan_hit_rate", 0.0)
        coarse = db.get("coarse", {}).get("plan_hit_rate", 0.0)
        gate.check("dict_buckets: tight hit rate >= 0.5 x coarse",
                   tight >= 0.5 * coarse,
                   f"tight={tight} coarse={coarse}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fresh-device", required=True,
                    help="BENCH_device.json from the fresh smoke run")
    ap.add_argument("--baseline-device", default="BENCH_device.json",
                    help="committed baseline (default: BENCH_device.json)")
    ap.add_argument("--fresh-multiquery", default=None,
                    help="optional fresh bench_multiquery.py --out report")
    ap.add_argument("--fresh-stream", default=None,
                    help="optional fresh bench_stream.py --out report; "
                         "compared against the 'stream' section of the "
                         "device baseline")
    ap.add_argument("--stream-tolerance", type=float, default=0.5,
                    help="floor/ceiling fraction for the streaming "
                         "delta-reuse / re-upload gates (default 0.5 — a "
                         "collapse detector like the device speedup "
                         "floors)")
    ap.add_argument("--min-stream-speedup", type=float, default=1.0,
                    help="absolute floor on the host-lockstep streaming "
                         "steady-state speedup vs rebuild-per-round "
                         "(default 1.0: delta reuse must not lose; smoke "
                         "tables straddle ~1.1-1.2 because fixed per-round "
                         "costs dominate at 50k rows — pass 1.2 when "
                         "gating a full-scale run)")
    ap.add_argument("--speedup-tolerance", type=float, default=0.2,
                    help="fresh speedup must reach this fraction of the "
                         "baseline speedup (default 0.2 — a coarse "
                         "collapse detector: smoke tables and CI machines "
                         "differ from the committed 1M-row baseline and "
                         "small batches are noisy; the sync/fallback "
                         "contract checks are exact)")
    ap.add_argument("--min-warm-speedup", type=float, default=3.0,
                    help="floor on the warm-restart first-drain speedup "
                         "(cold server vs restart warmed from the "
                         "persisted plan/tape/XLA caches; default 3.0)")
    ap.add_argument("--min-multiquery-speedup", type=float, default=1.0,
                    help="floor on the batched-vs-independent multiquery "
                         "speedup (default 1.0: batching must still pay)")
    args = ap.parse_args()

    with open(args.fresh_device) as f:
        fresh = json.load(f)
    with open(args.baseline_device) as f:
        base = json.load(f)
    gate = Gate()
    print(f"device: {args.fresh_device} (rows={fresh.get('rows')}) vs "
          f"baseline {args.baseline_device} (rows={base.get('rows')})")
    check_device(gate, fresh, base, args.speedup_tolerance)
    if args.fresh_multiquery:
        with open(args.fresh_multiquery) as f:
            mq = json.load(f)
        print(f"multiquery: {args.fresh_multiquery} "
              f"(rows={mq.get('rows')}, queries={mq.get('queries')})")
        check_multiquery(gate, mq, args.min_multiquery_speedup)
    if args.fresh_stream:
        with open(args.fresh_stream) as f:
            stream = json.load(f)
        base_stream = base.get("stream", {})
        print(f"stream: {args.fresh_stream} "
              f"(rows={stream.get('rows_initial')}) vs baseline stream "
              f"section (rows={base_stream.get('rows_initial')})")
        check_stream(gate, stream, base_stream, args.stream_tolerance,
                     args.min_stream_speedup, args.min_warm_speedup)
    return gate.report()


if __name__ == "__main__":
    sys.exit(main())
