"""Streaming-ingest benchmark: append-heavy serving vs rebuild-from-scratch.

Interleaves row appends with fixed-template query batches over one table
(strings included, so dictionary merges run on every append) and compares

* **stream** — one long-lived :class:`StreamSession` draining through the
  device-resident lockstep tape executor (one bundled host sync per batch):
  cached atom results splice in only appended rows, the device backend
  re-uploads only dirty tail blocks, and the plan cache persists;
* **naive**  — a fresh ``QuerySession`` per round (the pre-ingest behavior:
  full column re-upload, full-table atom evaluation, cold plan cache).

Reports the delta-reuse ratio (fraction of cached-atom rows served without
re-evaluation), re-upload bytes vs the naive full uploads, per-batch sync
counts, and a tape-rebind microsection (plan-cache hits skipping the
trace/DCE/slot-allocation pipeline on the per-query tape path).  The
``stream`` section of the committed ``BENCH_device.json`` baseline is
produced with ``--update-baseline`` and gated by
``benchmarks/check_regression.py --fresh-stream``.

    PYTHONPATH=src python benchmarks/bench_stream.py --rows 1000000 \
        --update-baseline BENCH_device.json
    PYTHONPATH=src python benchmarks/bench_stream.py --smoke   # CI
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

from repro.columnar import (DrainPolicy, ExecConfig, LatencyWindow,
                            QuerySession, StreamSession, Table,
                            make_forest_table, random_tree, run_query)
from repro.core import And, Atom, normalize
from repro.runtime import faults


def _rows_like(table, n, seed):
    src = make_forest_table(n, n_dup=1, seed=seed, strings=True)
    return {name: src.columns[name] for name in table.columns}


def bench_stream(args, engine: str) -> dict:
    table = make_forest_table(args.rows, n_dup=1, seed=7, strings=True)
    rng = np.random.default_rng(0)
    pool = [random_tree(table, args.atoms, args.depth, rng)
            for _ in range(args.templates)]
    queries = [pool[rng.integers(args.templates)]
               for _ in range(args.batch)]
    n_append = max(int(args.rows * args.append_frac), 1)

    # max_pending is one past the batch so the timed drain() below is the
    # one that runs the batch (admission alone must stay cheap)
    cfg = StreamSession.DEFAULT_CONFIG.replace(engine=engine,
                                               block=args.block)
    stream = StreamSession(table, config=cfg, max_pending=args.batch + 1)

    stream_ms = naive_ms = 0.0
    reupload_bytes = naive_upload_bytes = 0.0
    syncs_per_batch = []
    identical = True
    initial_upload = None
    for rnd in range(args.rounds):
        if rnd:
            stream.append(_rows_like(table, n_append, seed=100 + rnd))
            # statistics rebuild lazily after an append (quantile sketches
            # are not yet mergeable — ROADMAP follow-up); warm them OUTSIDE
            # the timers so whoever runs first doesn't eat the shared cost
            for name in table.columns:
                table.stats(name)
        for q in queries:
            stream.submit(q)
        t0 = time.perf_counter()
        res = stream.drain()
        if rnd:
            # round 0 seeds jit caches / uploads / plans for BOTH sides;
            # the comparison is the append-interleaved steady state
            stream_ms += (time.perf_counter() - t0) * 1e3
        be = stream.session._backend
        if initial_upload is None:
            initial_upload = res.stats.upload_bytes
        else:
            reupload_bytes += res.stats.upload_bytes
        syncs_per_batch.append(be.host_syncs if rnd == 0
                               else be.host_syncs - sum(syncs_per_batch))

        # naive: rebuild everything for the same snapshot
        naive = QuerySession(table, config=ExecConfig(
            planner="deepfish", engine=engine, block=args.block,
            batched=True))
        t0 = time.perf_counter()
        nres = naive.execute(queries)
        if rnd:
            naive_ms += (time.perf_counter() - t0) * 1e3
            naive_upload_bytes += nres.stats.upload_bytes

        identical &= all(np.array_equal(a, b) for a, b in
                         zip(res.bitmaps, nres.bitmaps))
        if rnd in (0, args.rounds - 1):
            for q in queries[:2]:
                want, _, _ = run_query(q, table, config=ExecConfig(
                    planner="deepfish"))
                identical &= np.array_equal(
                    res.bitmaps[queries.index(q)], want)

    st = stream.stats
    out = {
        "rows_initial": args.rows,
        "rows_final": table.n_records,
        "rounds": args.rounds,
        "append_rows": n_append,
        "queries": args.batch,
        "engine": engine,
        "stream_ms": round(stream_ms, 3),
        "naive_ms": round(naive_ms, 3),
        "speedup": round(naive_ms / stream_ms, 2) if stream_ms else 0.0,
        "delta_reuse_ratio": round(st.delta_reuse_ratio, 4),
        "atoms_delta_extended": st.atoms_delta_extended,
        "initial_upload_bytes": initial_upload,
        "reupload_bytes": reupload_bytes,
        "naive_upload_bytes": naive_upload_bytes,
        "reupload_fraction": round(reupload_bytes / naive_upload_bytes, 4)
        if naive_upload_bytes else 0.0,
        "host_syncs_per_batch": max(syncs_per_batch),
        "identical": bool(identical),
    }
    return out


def bench_selective_stream(args) -> dict:
    """Selective-stream section: tail-window monitors, beyond-the-head
    alert probes and historical ranges over an append-only stream (rows
    arrive in ``seq`` order, so zone maps decide most blocks), drained
    through the device lockstep executor with zone pruning on vs off.
    The verdict masks are runtime inputs: every append round reuses the
    same jitted programs."""
    rows, block = args.rows, args.block
    # rounds 0-1 are warmup (round 1 is the first append-interleaved drain,
    # where cache-hit/delta paths jit-compile); timing starts at round 2
    rounds = max(args.rounds, 3)
    n_append = max(int(rows * args.append_frac), 1)

    def mk(n, start, seed):
        rng = np.random.default_rng(seed)
        return {
            "seq": (start + np.arange(n)).astype(np.float32),
            "val": rng.normal(size=n).astype(np.float32),
            "load": np.abs(rng.normal(size=n) * 50).astype(np.float32),
        }

    def round_queries(hi):
        window = rows * 0.02
        qs = []
        for j in range(args.batch):
            if j % 3 == 0:        # tail-window monitor
                qs.append(normalize(And([
                    Atom("seq", "ge", hi - window, selectivity=0.02),
                    Atom("val", "gt", 0.0, selectivity=0.5)])))
            elif j % 3 == 1:      # alert probe beyond the stream head
                qs.append(normalize(And([
                    Atom("seq", "ge", hi * 1.5 + j, selectivity=0.001),
                    Atom("load", "gt", 100.0, selectivity=0.01)])))
            else:                 # historical range
                qs.append(normalize(And([
                    Atom("seq", "lt", rows * 0.2, selectivity=0.2),
                    Atom("val", "lt", -0.5, selectivity=0.3)])))
        return qs

    out = {"rows_initial": rows, "rounds": rounds, "queries": args.batch,
           "engine": args.engine}
    finals = {}
    # one full untimed pass of BOTH flavors first: jit compilation is
    # process-wide and decays over rounds, so whichever flavor runs first
    # would otherwise eat the shared warmup inside its timers
    for warm, zp in ((True, True), (True, False),
                     (False, True), (False, False)):
        table = Table(mk(rows, 0, seed=5))
        cfg = StreamSession.DEFAULT_CONFIG.replace(
            engine=args.engine, block=block, zone_prune=zp)
        stream = StreamSession(table, config=cfg,
                               max_pending=args.batch + 1)
        ms = 0.0
        syncs = []
        res = None
        for rnd in range(rounds):
            if rnd:
                stream.append(mk(n_append, table.n_records, seed=50 + rnd))
                for name in table.columns:
                    table.stats(name)
            queries = round_queries(float(table.n_records))
            for q in queries:
                stream.submit(q)
            be = stream.session._backend
            s0 = be.host_syncs if be is not None else 0
            t0 = time.perf_counter()
            res = stream.drain()
            if rnd >= 2:
                ms += (time.perf_counter() - t0) * 1e3
            be = stream.session._backend
            syncs.append(be.host_syncs - s0)
        if warm:
            continue
        key = "pruned" if zp else "unpruned"
        out[key + "_ms"] = round(ms, 3)
        finals[key] = (res.bitmaps, queries, table)
        if zp:
            be = stream.session._backend
            out["blocks_pruned"] = be.blocks_pruned
            # JaxBlockBackend (--engine jax/pallas) has no fallback counter
            out["host_fallbacks"] = getattr(be, "host_fallbacks", 0)
            out["host_syncs_per_batch"] = max(syncs)
    out["speedup"] = (round(out["unpruned_ms"] / out["pruned_ms"], 2)
                      if out["pruned_ms"] else 0.0)
    pb, pq, ptable = finals["pruned"]
    ub, _, _ = finals["unpruned"]
    identical = all(np.array_equal(a, b) for a, b in zip(pb, ub))
    for j in (0, 1, 2):
        want, _, _ = run_query(pq[j], ptable, config=ExecConfig(
            planner="deepfish"))
        identical &= np.array_equal(pb[j], want)
    out["identical"] = bool(identical)
    return out


def bench_rebind(args) -> dict:
    """Tape-reuse microsection: per-query compiled-tape path, second pass
    served by rebinding cached host tapes (no re-trace/DCE/slot-alloc)."""
    table = make_forest_table(min(args.rows, 100_000), n_dup=1, seed=7)
    rng = np.random.default_rng(1)
    pool = [random_tree(table, args.atoms, args.depth, rng)
            for _ in range(args.templates)]
    queries = [pool[rng.integers(args.templates)]
               for _ in range(args.batch)]
    # feedback off: runtime-corrected selectivities legitimately re-key (and
    # so replan) queries between passes — that loop is measured by the drift
    # section; this microsection isolates pure tape rebinding
    sess = QuerySession(table, config=ExecConfig(
        planner="deepfish", engine="tape", block=args.block,
        batched="auto", persist_atom_cache=False, feedback=False))
    t0 = time.perf_counter()
    sess.execute(queries)                    # cold: trace + compile + jit
    cold_ms = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    res = sess.execute(queries)              # warm: rebind cached tapes
    warm_ms = (time.perf_counter() - t0) * 1e3
    return {
        "queries": args.batch,
        "cold_ms": round(cold_ms, 3),
        "warm_ms": round(warm_ms, 3),
        "tape_cache_hits": res.stats.tape_cache_hits,
        "plan_cache_hits": res.stats.plan_cache_hits,
    }


def _probe_queries(table, args):
    rng = np.random.default_rng(11)
    return [random_tree(table, args.atoms, args.depth, rng)
            for _ in range(8)]


def _first_drain_probe(args) -> None:
    """Subprocess mode behind ``--first-drain-probe DIR``: build a fresh
    process, warm it from DIR (plan/tape/feedback + persistent XLA cache),
    time the FIRST drain, flush caches back, and print a one-line JSON
    verdict.  Run twice against the same DIR by ``bench_slo`` — the first
    run is the cold server, the second the warm restart."""
    rows = min(args.rows, 120_000)
    table = make_forest_table(rows, n_dup=1, seed=7)
    queries = _probe_queries(table, args)
    cfg = StreamSession.DEFAULT_CONFIG.replace(
        engine=args.engine, block=args.block, batched="auto")
    stream = StreamSession(table, config=cfg,
                           max_pending=len(queries) + 1,
                           cache_dir=args.first_drain_probe)
    futs = [stream.submit(q) for q in queries]
    t0 = time.perf_counter()
    res = stream.drain()
    ms = (time.perf_counter() - t0) * 1e3
    checksum = int(sum(int(f.mask().sum()) for f in futs))
    out = {
        "first_drain_ms": round(ms, 3),
        "tape_cache_hits": res.stats.tape_cache_hits,
        "plan_cache_hits": res.stats.plan_cache_hits,
        "restored_plans": stream.restore_info.get("plans", 0),
        "checksum": checksum,
    }
    stream.close()
    print(json.dumps(out))


def _run_probe(args, cache_dir: str) -> dict:
    """Launch ``--first-drain-probe`` in a fresh interpreter (warm-restart
    timing only means anything across a process boundary: jit caches,
    traced programs and plan caches all die with the process).  The
    probe's XLA cache is ``cache_dir/xla``, private to one measurement, so
    its first probe compiles from nothing whatever earlier runs cached."""
    here = os.path.abspath(__file__)
    src = os.path.join(os.path.dirname(os.path.dirname(here)), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache_dir, "xla")
    cmd = [sys.executable, here, "--first-drain-probe", cache_dir,
           "--rows", str(args.rows), "--atoms", str(args.atoms),
           "--depth", str(args.depth), "--block", str(args.block),
           "--engine", args.engine]
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=600)
    if proc.returncode != 0:
        raise SystemExit(f"warm-restart probe failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_slo(args) -> dict:
    """Serving-SLO section (``--slo``): admit-to-result latency under the
    background drainer, graceful degradation under injected device faults
    (bit-identical, zero lost futures), the one-bundled-sync contract with
    tombstones live (warm-vs-cold restart is :func:`bench_warm_restart`,
    run first)."""
    rows = min(args.rows, 120_000)
    table = make_forest_table(rows, n_dup=1, seed=7)
    rng = np.random.default_rng(3)
    pool = [random_tree(table, args.atoms, args.depth, rng)
            for _ in range(max(args.templates, 4))]
    queries = [pool[i % len(pool)] for i in range(args.batch)]
    out = {}

    # -- admit-to-result latency under the background drainer ----------------
    # per-query tapes (batched="auto") so the deadline drains' varying batch
    # compositions reuse cached compiled tapes instead of retracing
    policy = DrainPolicy(max_wait_ms=40.0, interactive_wait_ms=4.0)
    cfg = StreamSession.DEFAULT_CONFIG.replace(
        engine=args.engine, block=args.block, batched="auto")
    with StreamSession(table, config=cfg, max_pending=args.batch,
                       background=True, policy=policy) as stream:
        for f in [stream.submit(q) for q in pool]:      # jit/plan warmup
            f.result(timeout=300.0)
        stream.stats.latency = LatencyWindow()          # drop warmup samples
        futs = []
        for i in range(args.batch * 4):
            lane = "interactive" if i % 4 == 0 else "bulk"
            futs.append(stream.submit(pool[i % len(pool)], lane=lane))
            time.sleep(0.002)
        for f in futs:
            f.result(timeout=300.0)
        lat = stream.stats.latency
        out["latency"] = {
            "samples": lat.count,
            "p50_ms": round(lat.p50, 3),
            "p99_ms": round(lat.p99, 3),
            "deadline_drains": stream._drainer.deadline_drains,
        }

    # -- graceful degradation under an injected device fault -----------------
    faults.fault_plane().clear()
    cfg = StreamSession.DEFAULT_CONFIG.replace(engine=args.engine,
                                               block=args.block)
    with StreamSession(table, config=cfg,
                       max_pending=args.batch + 1) as clean:
        cf = [clean.submit(q) for q in queries]
        clean.drain()
        baseline = [f.result() for f in cf]

    with StreamSession(table, config=cfg,
                       max_pending=args.batch + 1) as faulty:
        wf = [faulty.submit(q) for q in queries]
        faulty.drain()                                  # clean device drain
        for f in wf:
            f.result()
        with faults.inject("device.dispatch", exc=faults.DeviceFault,
                           times=1):
            ff = [faulty.submit(q) for q in queries]
            faulty.drain()
        lost = sum(0 if f.done() else 1 for f in ff)
        identical = lost == 0 and all(
            np.array_equal(f.result(), b) for f, b in zip(ff, baseline))
        out["faults"] = {
            "degraded_batches": faulty.stats.degraded_batches,
            "quarantined_queries": faulty.stats.quarantined_queries,
            "retries": faulty.stats.retries,
            "lost_futures": lost,
            "identical": bool(identical),
        }

    # -- the one-bundled-sync contract survives tombstones -------------------
    cfg = StreamSession.DEFAULT_CONFIG.replace(engine=args.engine,
                                               block=args.block)
    with StreamSession(table, config=cfg,
                       max_pending=args.batch + 1) as ts:
        for q in queries:
            ts.submit(q)
        ts.drain()                                      # warm the device path
        n_dead = rows // 10
        ts.delete(np.arange(n_dead))
        be = ts.session._backend
        s0 = be.host_syncs
        tf = [ts.submit(q) for q in queries]
        ts.drain()
        out["sync_per_drain_with_tombstones"] = be.host_syncs - s0
        out["tombstones_respected"] = bool(
            not any(f.mask()[:n_dead].any() for f in tf))
        out["degraded_with_tombstones"] = ts.stats.degraded_batches

    return out


def bench_warm_restart(args) -> dict:
    """Warm-vs-cold first drain across a real process boundary (the
    ``--slo`` section's restart half).  Every probe is a child process,
    so this runs before the parent initialises any JAX backend: on an
    accelerator host the parent would otherwise hold the device the
    children need.  All probes share one temporary directory: its plan /
    tape / feedback pickles and, through ``JAX_COMPILATION_CACHE_DIR``, its
    XLA cache (:func:`_run_probe`), so the first probe is cold in both and
    the later ones are warm in both."""
    cache_dir = tempfile.mkdtemp(prefix="stream-warm-")
    cold = _run_probe(args, cache_dir)
    # each probe process is a genuine warm restart; best-of-two damps
    # scheduler noise on the short warm drain (the cold run's compile time
    # dwarfs the same noise)
    warm_runs = [_run_probe(args, cache_dir) for _ in range(2)]
    warm = min(warm_runs, key=lambda r: r["first_drain_ms"])
    speedup = (cold["first_drain_ms"] / warm["first_drain_ms"]
               if warm["first_drain_ms"] else 0.0)
    return {
        "cold_first_drain_ms": cold["first_drain_ms"],
        "warm_first_drain_ms": warm["first_drain_ms"],
        "warm_first_drain_ms_runs": [r["first_drain_ms"]
                                     for r in warm_runs],
        "warm_speedup": round(speedup, 2),
        "tape_cache_hits_warm": warm["tape_cache_hits"],
        "plan_cache_hits_warm": warm["plan_cache_hits"],
        "restored_plans_warm": warm["restored_plans"],
        "identical": all(r["checksum"] == cold["checksum"]
                         for r in warm_runs),
    }


def bench_durable(args) -> dict:
    """Durability section (``--durable``): the same append-interleaved
    drain loop with the WAL off vs on (group commit, the serving
    default), then a real close/recover cycle over the durable state.

    The contract halves are exact: the durable arm's bitmaps are
    bit-identical to the in-memory arm's every round, and a session
    recovered from the snapshot + WAL tail answers the same queries
    bit-identically to the live pre-close session.  The overhead half is
    a timing (best-of over the timed rounds, the ``obs`` idiom): the
    group-commit fsync discipline must stay within a few percent of the
    in-memory drain — the ``<= 10%`` ceiling is gated on the committed
    full-scale baseline by ``check_regression.py``."""
    rows = min(args.rows, 400_000)
    rounds = max(args.rounds, 3)
    n_append = max(int(rows * args.append_frac), 1)
    table_seed = make_forest_table(rows, n_dup=1, seed=7, strings=True)
    rng = np.random.default_rng(4)
    pool = [random_tree(table_seed, args.atoms, args.depth, rng)
            for _ in range(args.templates)]
    queries = [pool[i % len(pool)] for i in range(args.batch)]
    cfg = StreamSession.DEFAULT_CONFIG.replace(engine=args.engine,
                                               block=args.block)

    def run(durable_dir):
        stream = StreamSession(
            make_forest_table(rows, n_dup=1, seed=7, strings=True),
            config=cfg, max_pending=args.batch + 1,
            durable=durable_dir, wal_sync="group", snapshot_every=None)
        table = stream.table
        times, bitmaps = [], None
        for rnd in range(rounds):
            t0 = time.perf_counter()
            if rnd:         # append INSIDE the timer: WAL logging + the
                stream.append(_rows_like(table, n_append,   # group commit
                              seed=200 + rnd))              # are the cost
            futs = [stream.submit(q) for q in queries]
            stream.drain()
            if rnd:
                times.append((time.perf_counter() - t0) * 1e3)
            if durable_dir and rnd == 1:
                # one explicit mid-history snapshot, OUTSIDE the timers:
                # every later append is a WAL-tail record, so the recovery
                # below is a genuine snapshot + tail replay
                stream.durability.snapshot()
            for name in table.columns:
                table.stats(name)
            bitmaps = futs
        return min(times), [f.result() for f in bitmaps], stream

    run(None)[2].close()     # untimed pass: process-wide jit warmup
    off_ms, off_bitmaps, off_stream = run(None)
    off_stream.close()
    data_dir = tempfile.mkdtemp(prefix="stream-durable-")
    on_ms, on_bitmaps, on_stream = run(data_dir)
    identical = all(np.array_equal(a, b)
                    for a, b in zip(off_bitmaps, on_bitmaps))

    # one more acknowledged append past the last snapshot, then crash the
    # session (close) and recover: snapshot + WAL-tail replay
    on_stream.append(_rows_like(on_stream.table, n_append, seed=999))
    final_futs = [on_stream.submit(q) for q in queries]
    on_stream.drain()
    live_final = [f.result() for f in final_futs]
    wal = on_stream.health()["wal"]
    # crash, don't close: StreamSession.close() would cut a final snapshot
    # (clean shutdown = zero replay).  Releasing the WAL handle after the
    # drain's group commit is exactly the kill -9 recovery scenario — the
    # mid-history snapshot plus a tail of acknowledged appends
    on_stream.durability.close()

    rec = StreamSession(None, config=cfg, max_pending=args.batch + 1,
                        durable=data_dir)
    info = rec.recovery_info
    rec_futs = [rec.submit(q) for q in queries]
    rec.drain()
    recovery_identical = (
        rec.table.n_records == rows + rounds * n_append
        and all(np.array_equal(np.asarray(f.result()), b)
                for f, b in zip(rec_futs, live_final)))
    for q in queries[:2]:       # and against the planner-level oracle
        want, _, _ = run_query(q, rec.table,
                               config=ExecConfig(planner="deepfish"))
        recovery_identical &= np.array_equal(
            np.asarray(rec_futs[queries.index(q)].result()), want)
    recovered_rows = rec.table.n_records
    rec.close()
    return {
        "rows_initial": rows,
        "rounds": rounds,
        "append_rows": n_append,
        "queries": args.batch,
        "engine": args.engine,
        "wal_sync": "group",
        "off_ms": round(off_ms, 3),
        "on_ms": round(on_ms, 3),
        "overhead_pct": round((on_ms / off_ms - 1.0) * 100.0, 2)
        if off_ms else 0.0,
        "identical": bool(identical),
        "wal_committed_seq": wal["committed_seq"],
        "wal_uncommitted": wal["uncommitted"],
        "snapshots": wal["snapshots"],
        "recovered_rows": recovered_rows,
        "snapshot_seq": info["snapshot_seq"],
        "replayed_records": info["replayed_records"],
        "truncated_records": info["truncated_records"],
        "recovery_ms": round(info["recovery_ms"], 3),
        "recovery_identical": bool(recovery_identical),
    }


def bench_obs_stream(args) -> dict:
    """Observability overhead on the serving path: the same warm drain loop
    with telemetry+trace off vs on (caller-owned registry + tracer).  The
    on-arm additionally exercises the per-drain publish, explain retention
    and the latency histogram — everything a live ``/metrics`` scrape
    would see — and must stay bit-identical at one bundled sync/drain."""
    from repro.columnar import Tracer
    from repro.runtime.telemetry import MetricsRegistry

    rows = min(args.rows, 200_000)
    table_seed = make_forest_table(rows, n_dup=1, seed=7, strings=True)
    rng = np.random.default_rng(2)
    pool = [random_tree(table_seed, args.atoms, args.depth, rng)
            for _ in range(args.templates)]
    queries = [pool[i % len(pool)] for i in range(args.batch)]
    rounds = max(args.rounds, 3)

    def run(telemetry, trace):
        table = make_forest_table(rows, n_dup=1, seed=7, strings=True)
        cfg = StreamSession.DEFAULT_CONFIG.replace(
            engine=args.engine, block=args.block,
            telemetry=telemetry, trace=trace)
        stream = StreamSession(table, config=cfg,
                               max_pending=args.batch + 1)
        times, syncs, bitmaps = [], [], []
        for rnd in range(rounds):
            futs = [stream.submit(q) for q in queries]
            be_syncs0 = (stream.session._backend.host_syncs
                         if stream.session._backend is not None else 0)
            t0 = time.perf_counter()
            stream.drain()
            if rnd:                       # round 0 seeds jit/plans/uploads
                times.append((time.perf_counter() - t0) * 1e3)
            syncs.append(stream.session._backend.host_syncs - be_syncs0)
            if rnd == rounds - 1:
                bitmaps = [f.result() for f in futs]
        stream.close()
        # best-of the timed drains (the repo's idiom): single ~100ms+
        # drains are noisy enough that a sum would swamp a few-percent
        # telemetry delta in scheduler jitter
        return min(times), max(syncs[1:]), bitmaps

    run(False, False)        # untimed: process-wide jit warmup is shared
    off_ms, off_syncs, off_bitmaps = run(False, False)
    reg, tr = MetricsRegistry(), Tracer()
    on_ms, on_syncs, on_bitmaps = run(reg, tr)
    spans = tr.drain()
    snap = reg.snapshot()
    lat = snap.get("repro_query_latency_ms", {})
    return {
        "rounds": rounds,
        "queries": args.batch,
        "engine": args.engine,
        "off_ms": round(off_ms, 3),
        "on_ms": round(on_ms, 3),
        "overhead_pct": round((on_ms / off_ms - 1.0) * 100.0, 2)
        if off_ms else 0.0,
        "identical": bool(all(np.array_equal(a, b) for a, b in
                              zip(off_bitmaps, on_bitmaps))),
        "host_syncs_per_drain_off": off_syncs,
        "host_syncs_per_drain_on": on_syncs,
        "metrics_registered": len(reg.names()),
        "latency_samples": sum(s.get("count", 0)
                               for s in lat.get("samples", [])),
        "spans_total": len(spans),
        "drain_spans": sum(1 for s in spans if s.name == "stream.drain"),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--append-frac", type=float, default=0.02)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--templates", type=int, default=8)
    ap.add_argument("--atoms", type=int, default=6)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--block", type=int, default=8192)
    ap.add_argument("--engine", default="tape",
                    choices=["jax", "pallas", "tape", "tape-pallas"],
                    help="engine for the contract section (the device "
                         "lockstep executor: one bundled sync per drain)")
    ap.add_argument("--host-engine", default="jax",
                    help="engine for the host-lockstep timing section "
                         "(where delta reuse shows up as saved kernel "
                         "work even on CPU)")
    ap.add_argument("--out", default="BENCH_stream.json")
    ap.add_argument("--update-baseline", default=None, metavar="DEVICE_JSON",
                    help="also merge the report as the 'stream' section of "
                         "the committed device baseline")
    ap.add_argument("--smoke", action="store_true",
                    help="CI preset: small table, few rounds")
    ap.add_argument("--slo", action="store_true",
                    help="also run the serving-SLO section: drainer "
                         "latency percentiles, fault-injected degradation, "
                         "sync contract under tombstones, warm-vs-cold "
                         "restart")
    ap.add_argument("--durable", action="store_true",
                    help="also run the durability section: WAL group-"
                         "commit overhead on the steady-state stream, "
                         "close/recover cycle with bit-identical results, "
                         "recovery wall time")
    ap.add_argument("--merge-durable", default=None, metavar="DEVICE_JSON",
                    help="run ONLY the durability section and merge it as "
                         "the 'durable' subsection of the committed device "
                         "baseline's stream section (leaves every other "
                         "committed figure untouched)")
    ap.add_argument("--obs", dest="obs", action="store_true", default=True,
                    help="run the observability overhead section on the "
                         "serving path (default: on)")
    ap.add_argument("--no-obs", dest="obs", action="store_false")
    ap.add_argument("--first-drain-probe", default=None, metavar="DIR",
                    help=argparse.SUPPRESS)   # internal: see bench_slo
    args = ap.parse_args()
    if args.smoke:
        args.rows, args.rounds, args.batch = 50_000, 3, 8
        args.templates = 2
    if args.first_drain_probe:
        _first_drain_probe(args)
        return

    def show_durable(du):
        print(f"durable [{du['engine']}]: off {du['off_ms']:.1f} ms  vs  "
              f"WAL-on {du['on_ms']:.1f} ms  ->  "
              f"{du['overhead_pct']:+.1f}% overhead "
              f"(group commit, seq {du['wal_committed_seq']}, "
              f"{du['snapshots']} snapshots)  identical={du['identical']}")
        print(f"  recovery: {du['recovered_rows']} rows from snapshot seq "
              f"{du['snapshot_seq']} + {du['replayed_records']} replayed "
              f"records in {du['recovery_ms']:.1f} ms  "
              f"identical={du['recovery_identical']}")

    if args.merge_durable:
        du = bench_durable(args)
        show_durable(du)
        if not (du["identical"] and du["recovery_identical"]):
            raise SystemExit("FAIL: durable stream diverged from the "
                             "in-memory arm or recovery was not "
                             "bit-identical; baseline NOT updated")
        with open(args.merge_durable) as f:
            base = json.load(f)
        base.setdefault("stream", {})["durable"] = du
        with open(args.merge_durable, "w") as f:
            json.dump(base, f, indent=2)
        print(f"updated stream.durable section of {args.merge_durable}")
        return

    def show(name, sec):
        print(f"{name} [{sec['engine']}]: {sec['rounds']} rounds x "
              f"{sec['queries']} queries, {sec['rows_initial']} -> "
              f"{sec['rows_final']} rows (+{sec['append_rows']}/round)")
        print(f"  stream {sec['stream_ms']:.1f} ms  vs  naive "
              f"{sec['naive_ms']:.1f} ms  ->  {sec['speedup']:.2f}x  "
              f"identical={sec['identical']}")
        print(f"  delta reuse {sec['delta_reuse_ratio']:.1%} "
              f"({sec['atoms_delta_extended']} atom splices), re-upload "
              f"{sec['reupload_bytes'] / 1e6:.2f} MB vs naive "
              f"{sec['naive_upload_bytes'] / 1e6:.2f} MB "
              f"(fraction {sec['reupload_fraction']:.3f}), "
              f"{sec['host_syncs_per_batch']:g} sync/batch")

    # child processes first, before this process touches a device
    warm_restart = bench_warm_restart(args) if args.slo else None
    report = bench_stream(args, args.engine)
    show("stream", report)
    report["host"] = bench_stream(args, args.host_engine)
    show("stream host", report["host"])

    report["selective"] = bench_selective_stream(args)
    sel = report["selective"]
    print(f"selective [{sel['engine']}]: pruned {sel['pruned_ms']:.1f} ms "
          f"vs unpruned {sel['unpruned_ms']:.1f} ms  ->  "
          f"{sel['speedup']:.2f}x  ({sel['blocks_pruned']:.0f} blocks "
          f"pruned, {sel['host_fallbacks']} fallbacks, "
          f"{sel['host_syncs_per_batch']:g} sync/batch)  "
          f"identical={sel['identical']}")

    report["rebind"] = bench_rebind(args)
    rb = report["rebind"]
    print(f"  tape rebind: cold {rb['cold_ms']:.1f} ms -> warm "
          f"{rb['warm_ms']:.1f} ms ({rb['tape_cache_hits']}/{rb['queries']} "
          f"tapes rebound)")

    if args.obs:
        report["obs"] = bench_obs_stream(args)
        ob = report["obs"]
        print(f"obs [{ob['engine']}]: off {ob['off_ms']:.1f} ms  vs  on "
              f"{ob['on_ms']:.1f} ms  ->  {ob['overhead_pct']:+.1f}% "
              f"overhead, {ob['metrics_registered']} metrics, "
              f"{ob['latency_samples']} latency samples, "
              f"{ob['drain_spans']} drain spans, syncs/drain "
              f"{ob['host_syncs_per_drain_off']:g}->"
              f"{ob['host_syncs_per_drain_on']:g}  "
              f"identical={ob['identical']}")

    if args.durable:
        report["durable"] = bench_durable(args)
        show_durable(report["durable"])

    if args.slo:
        report["slo"] = bench_slo(args)
        report["slo"]["warm_restart"] = warm_restart
        slo = report["slo"]
        lat, flt, wr = slo["latency"], slo["faults"], slo["warm_restart"]
        print(f"slo: admit-to-result p50 {lat['p50_ms']:.1f} ms / p99 "
              f"{lat['p99_ms']:.1f} ms over {lat['samples']} queries "
              f"({lat['deadline_drains']} deadline drains)")
        print(f"  faults: {flt['degraded_batches']} degraded batch(es), "
              f"{flt['retries']} retries, {flt['lost_futures']} lost, "
              f"identical={flt['identical']}")
        print(f"  tombstones: {slo['sync_per_drain_with_tombstones']:g} "
              f"sync/drain, respected="
              f"{slo['tombstones_respected']}")
        print(f"  warm restart: cold {wr['cold_first_drain_ms']:.0f} ms -> "
              f"warm {wr['warm_first_drain_ms']:.0f} ms "
              f"({wr['warm_speedup']:.2f}x, "
              f"{wr['tape_cache_hits_warm']} tapes / "
              f"{wr['restored_plans_warm']} plans restored) "
              f"identical={wr['identical']}")

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    if args.update_baseline:
        with open(args.update_baseline) as f:
            base = json.load(f)
        base["stream"] = report
        with open(args.update_baseline, "w") as f:
            json.dump(base, f, indent=2)
        print(f"updated 'stream' section of {args.update_baseline}")
    if not (report["identical"] and report["host"]["identical"]
            and report["selective"]["identical"]):
        raise SystemExit("FAIL: streaming results diverged from the "
                         "rebuild-from-scratch oracle")
    if not (report["selective"]["blocks_pruned"] > 0
            and report["selective"]["host_fallbacks"] == 0):
        raise SystemExit("FAIL: zone pruning inactive on the selective "
                         "stream (or the compiled path fell back)")
    if args.obs:
        ob = report["obs"]
        if not (ob["identical"]
                and ob["host_syncs_per_drain_off"]
                == ob["host_syncs_per_drain_on"]
                and ob["latency_samples"] > 0):
            raise SystemExit("FAIL: serving observability perturbed results "
                             "or sync counts, or published no latency "
                             "samples")
    if args.durable:
        du = report["durable"]
        if not (du["identical"] and du["recovery_identical"]
                and du["wal_uncommitted"] == 0):
            raise SystemExit("FAIL: durable stream diverged from the "
                             "in-memory arm, recovery was not "
                             "bit-identical, or a drain resolved futures "
                             "with uncommitted WAL records")
    if args.slo:
        slo = report["slo"]
        if not (slo["faults"]["identical"]
                and slo["faults"]["lost_futures"] == 0
                and slo["tombstones_respected"]
                and slo["warm_restart"]["identical"]):
            raise SystemExit("FAIL: serving SLO section diverged (degraded "
                             "batch, tombstone mask, or warm restart not "
                             "bit-identical / futures lost)")


if __name__ == "__main__":
    main()
