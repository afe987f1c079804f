"""Device-tape engine benchmark: one device program per query vs per-step
kernel dispatch.

Compares the compiled-tape engine (``engine="tape"``:
``core.tape.compile_tape`` + ``columnar.device.DeviceTapeBackend``, all
bitmaps device-resident, ONE host sync per query) against the per-step
``JaxBlockBackend`` (``engine="jax"``: one kernel dispatch + host bitmap
round-trip per plan step) on

* a single 16-atom mixed AND/OR tree over ``--rows`` records,
* a ``--batch``-query serving-shaped workload through ``QuerySession``
  (device-resident lockstep vs host-resident lockstep), and
* a dict-string workload (``strings`` section): a mixed 16-atom AND/OR tree
  with ~30% string atoms (equality / IN / prefix-LIKE / sort-order range)
  over a table with string attributes — the paper's showcase shape that PR 2
  could only run with one host fallback per string atom.  The
  dictionary-code rewrite keeps it ONE device program / ONE sync
  (``host_fallbacks == 0``); the unrewritten fallback path is timed
  alongside as ``norewrite_*`` for reference.

plus a differential sweep asserting the two engines produce bit-identical
bitmaps, and — with ``--sharded`` — a multi-device section run in a
subprocess under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(sharded-tape execution over {1, 2, 8} shards: bit-identicality, the
one-collective-sync contract, no-retrace appends, shard-local delta
re-upload).  Wall-clock is best-of ``--repeats`` after a warmup run (the tape
engine's compile cost is reported separately as ``tape_cold_ms``).  Writes
``BENCH_device.json`` (``--out``), which doubles as the committed baseline
for the CI regression gate (``benchmarks/check_regression.py``).

    PYTHONPATH=src python benchmarks/bench_device.py --rows 1000000
    PYTHONPATH=src python benchmarks/bench_device.py --smoke   # CI
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

from repro.columnar import (BitmapBackend, DeviceTapeBackend, ExecConfig,
                            JaxBlockBackend, QuerySession,
                            ShardedTapeBackend, Table, make_forest_table,
                            random_tree, rewrite_string_atoms, run_query)
from repro.columnar.device import _TAPE_PROGRAMS
from repro.columnar.table import annotate_selectivities
from repro.core import (PerAtomCostModel, compile_tape, deepfish,
                        execute_plan, plan_cost)
from repro.core.predicate import And, Atom, Or, atom_key, normalize, tree_copy
from repro.core.tape import ATOM, CHAIN


def _best_of(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_single(table, tree, repeats: int, block: int) -> dict:
    model = PerAtomCostModel()
    plan = deepfish(tree, model, total_records=table.n_records)

    jax_be = JaxBlockBackend(table, block=block, engine="jax")
    execute_plan(plan, jax_be)                       # warm column uploads
    jax_be.kernel_invocations = jax_be.host_syncs = 0
    base = execute_plan(plan, jax_be)
    jax_kernels, jax_syncs = jax_be.kernel_invocations, jax_be.host_syncs
    jax_ms = _best_of(lambda: execute_plan(plan, jax_be), repeats) * 1e3

    tape = compile_tape(plan)
    tape_be = DeviceTapeBackend(table, block=block)
    t0 = time.perf_counter()
    res = tape_be.run_tape(tape)                     # cold: compile included
    cold_ms = (time.perf_counter() - t0) * 1e3
    tape_be.device_dispatches = tape_be.host_syncs = 0
    tape_be.host_fallbacks = 0
    res = tape_be.run_tape(tape)
    tape_dispatches, tape_syncs = (tape_be.device_dispatches,
                                   tape_be.host_syncs)
    tape_ms = _best_of(lambda: tape_be.run_tape(tape), repeats) * 1e3

    identical = bool(np.array_equal(res, base))
    return {
        "atoms": tree.n,
        "tape_ops": len(tape.ops),
        "tape_chains": tape.n_chains,
        "jax_ms": round(jax_ms, 3),
        "tape_ms": round(tape_ms, 3),
        "tape_cold_ms": round(cold_ms, 3),
        "speedup": round(jax_ms / tape_ms, 2) if tape_ms else float("inf"),
        "jax_kernel_invocations": jax_kernels,
        "jax_host_syncs": jax_syncs,
        "tape_device_dispatches": tape_dispatches,
        "tape_host_syncs_per_query": tape_syncs,
        "host_fallbacks": tape_be.host_fallbacks,
        "identical": identical,
    }


def _string_workload_tree(table):
    """Mixed 16-atom AND/OR tree, 5/16 string atoms (eq / IN / prefix-LIKE /
    sort-order range) — the CH-benchmark-style disjunctive showcase."""
    def num(col, g):
        return Atom(col, "lt", table.value_at_selectivity(col, g),
                    selectivity=g)
    return normalize(Or([
        And([num("elevation_0", 0.4), num("slope_0", 0.5),
             Atom("cover_0", "eq", "spruce"),
             num("h_dist_road_0", 0.6)]),
        And([Atom("district_0", "in",
                  ("district_03", "district_04", "district_05")),
             num("hillshade_9am_0", 0.7), num("aspect_0", 0.5)]),
        And([num("h_dist_hydro_0", 0.3), Atom("cover_0", "like", "p%"),
             num("hillshade_noon_0", 0.6), num("v_dist_hydro_0", 0.5)]),
        And([Atom("district_0", "ge", "district_12"),
             Atom("cover_0", "in", ("fir", "hemlock", "larch", "oak")),
             num("hillshade_3pm_0", 0.5), num("h_dist_fire_0", 0.4),
             num("elevation_0", 0.7)]),
    ]))


def bench_strings(table, repeats: int, block: int) -> dict:
    """Dict-string workload: the rewritten one-device-program path (tape)
    vs the per-step block engine (jax, also rewritten) vs the PR 2
    fallback path (tape without the rewrite, one host sync per string
    atom).  Ground truth is the numpy oracle on the ORIGINAL tree."""
    model = PerAtomCostModel()
    tree = _string_workload_tree(table)
    annotate_selectivities(tree, table)
    n_strings = sum(1 for a in tree.atoms
                    if not np.issubdtype(table.columns[a.column].dtype,
                                         np.number))
    oracle = execute_plan(deepfish(tree, model,
                                   total_records=table.n_records),
                          BitmapBackend(table))

    rtree = rewrite_string_atoms(tree, table)
    rplan = deepfish(rtree, model, total_records=table.n_records)

    jax_be = JaxBlockBackend(table, block=block, engine="jax")
    execute_plan(rplan, jax_be)                      # warm column uploads
    jax_be.host_syncs = 0
    r_jax = execute_plan(rplan, jax_be)
    jax_syncs = jax_be.host_syncs
    jax_ms = _best_of(lambda: execute_plan(rplan, jax_be), repeats) * 1e3

    tape = compile_tape(rplan)
    tape_be = DeviceTapeBackend(table, block=block)
    t0 = time.perf_counter()
    tape_be.run_tape(tape)                           # cold: compile included
    cold_ms = (time.perf_counter() - t0) * 1e3
    tape_be.device_dispatches = tape_be.host_syncs = 0
    tape_be.host_fallbacks = 0
    r_tape = tape_be.run_tape(tape)
    dispatches, syncs = tape_be.device_dispatches, tape_be.host_syncs
    fallbacks = tape_be.host_fallbacks
    tape_ms = _best_of(lambda: tape_be.run_tape(tape), repeats) * 1e3

    # reference: the unrewritten PR 2 path (host gather per string atom)
    plan0 = deepfish(tree, model, total_records=table.n_records)
    tape0 = compile_tape(plan0)
    nr_be = DeviceTapeBackend(table, block=block)
    nr_be.run_tape(tape0)
    nr_be.host_syncs = nr_be.host_fallbacks = 0
    r_nr = nr_be.run_tape(tape0)
    nr_syncs, nr_fallbacks = nr_be.host_syncs, nr_be.host_fallbacks
    nr_ms = _best_of(lambda: nr_be.run_tape(tape0), repeats) * 1e3

    return {
        "atoms": tree.n,
        "string_atoms": n_strings,
        "tape_ops": len(tape.ops),
        "jax_ms": round(jax_ms, 3),
        "tape_ms": round(tape_ms, 3),
        "tape_cold_ms": round(cold_ms, 3),
        "norewrite_tape_ms": round(nr_ms, 3),
        "speedup": round(jax_ms / tape_ms, 2) if tape_ms else float("inf"),
        "norewrite_speedup": round(nr_ms / tape_ms, 2) if tape_ms
        else float("inf"),
        "jax_host_syncs": jax_syncs,
        "tape_device_dispatches": dispatches,
        "tape_host_syncs_per_query": syncs,
        "host_fallbacks": fallbacks,
        "norewrite_host_syncs": nr_syncs,
        "norewrite_host_fallbacks": nr_fallbacks,
        "identical": bool(np.array_equal(r_tape, oracle)
                          and np.array_equal(r_jax, oracle)
                          and np.array_equal(r_nr, oracle)),
    }


def _oracle_bitmap(table, tree):
    model = PerAtomCostModel()
    return execute_plan(deepfish(tree, model,
                                 total_records=table.n_records),
                        BitmapBackend(table))


def _selective_table(rows: int, block: int) -> Table:
    """Selective-stream shape: rows clustered by ingest order (sorted on
    one column, like time-ordered appends) plus a block-constant shard id —
    the layouts whose zone maps decide blocks outright."""
    base = make_forest_table(rows, n_dup=1, seed=7)
    order = np.argsort(base.columns["elevation_0"], kind="stable")
    cols = {k: v[order] for k, v in base.columns.items()}
    cols["shard_0"] = (np.arange(rows) // block).astype(np.float32)
    return Table(cols)


def _selective_trees(table, block: int):
    """Tail/shard-targeted queries: eq atoms on the block-constant shard
    column are fully zone-decided, ranges on the clustered column leave
    one MAYBE straddler — the selective-stream serving mix."""
    nblocks = max(table.n_records // block, 4)
    ele = table.columns["elevation_0"]
    cuts = [float(np.quantile(ele, q)) for q in (0.1, 0.5, 0.85)]

    def num(col, g):
        return Atom(col, "lt", table.value_at_selectivity(col, g),
                    selectivity=g)

    trees = []
    for i, k in enumerate((1, nblocks // 2, nblocks - 2)):
        trees.append(normalize(And([
            Atom("shard_0", "eq", float(k), selectivity=1.0 / nblocks),
            Or([num("slope_0", 0.5), num("hillshade_9am_0", 0.4)]),
        ])))
    for i, cut in enumerate(cuts):
        g = (0.1, 0.5, 0.85)[i]
        trees.append(normalize(And([
            Atom("elevation_0", "lt", cut, selectivity=g),
            Or([num("h_dist_road_0", 0.4), num("aspect_0", 0.6)]),
            num("h_dist_fire_0", 0.7),
        ])))
    # alert-style probes over windows the stream has not reached yet (and
    # shards past the tail): the guard's zone verdicts are NONE on every
    # block, the guarded branches then run on empty sets — the classic
    # small-materialized-aggregate win zone maps exist for (router /
    # monitoring rules that rarely fire).  The unpruned baseline pays the
    # full scans; the compiled pruned path skips them at runtime (masks
    # are data, so the same programs serve every round)
    top = float(ele.max())
    for j in range(3):
        trees.append(normalize(And([
            Atom("elevation_0", "gt", top * (1.05 + 0.05 * j),
                 selectivity=0.001),
            Or([num("v_dist_hydro_0", 0.3), num("h_dist_hydro_0", 0.4),
                num("hillshade_9am_0", 0.5)]),
            Or([num("slope_0", 0.5), num("aspect_0", 0.6)]),
            num("h_dist_fire_0", 0.6),
        ])))
    trees.append(normalize(And([
        Atom("shard_0", "eq", float(nblocks + 3), selectivity=0.001),
        Or([num("hillshade_3pm_0", 0.5), num("h_dist_fire_0", 0.5)]),
        Or([num("hillshade_noon_0", 0.6), num("h_dist_road_0", 0.5)]),
    ])))
    return trees


def bench_selective(rows: int, repeats: int, block: int) -> dict:
    """Zone-pruned compiled tapes vs the unpruned tape baseline on the
    selective-stream workload — the verdict masks are runtime inputs, so
    an append round reuses every compiled program (no retrace)."""
    table = _selective_table(rows, block)
    trees = _selective_trees(table, block)
    model = PerAtomCostModel()
    plans = [deepfish(t, model, total_records=table.n_records)
             for t in trees]
    tapes = [compile_tape(p) for p in plans]
    oracles = [_oracle_bitmap(table, t) for t in trees]

    results = {}
    for name, zp in (("pruned", True), ("unpruned", False)):
        be = DeviceTapeBackend(table, block=block, zone_prune=zp)
        for tp in tapes:
            be.run_tape(tp)                       # warm compiles + uploads
        be.host_syncs = be.device_dispatches = 0
        be.blocks_pruned = be.blocks_touched = 0.0
        got = [be.run_tape(tp) for tp in tapes]
        # snapshot per-pass counters BEFORE the timing loop: the committed
        # metrics must describe one pass over the suite, not depend on
        # --repeats
        syncs_per_query = be.host_syncs / len(tapes)
        blocks_pruned = be.blocks_pruned
        blocks_touched = be.blocks_touched
        # the pruned-vs-unpruned delta is smaller than the tape-vs-jax
        # gaps elsewhere in this file: take more samples against noise
        ms = _best_of(lambda: [be.run_tape(tp) for tp in tapes],
                      max(repeats, 5)) * 1e3
        results[name] = {
            "ms": ms, "backend": be, "bitmaps": got,
            "syncs_per_query": syncs_per_query,
            "blocks_pruned": blocks_pruned,
            "blocks_touched": blocks_touched,
            "identical": all(np.array_equal(a, b)
                             for a, b in zip(got, oracles)),
        }

    pr, un = results["pruned"], results["unpruned"]
    prb = pr["backend"]

    def _total_traces():
        # program count alone cannot see jax-level retraces (same cache
        # key, new input shapes): count the jit traces underneath too
        return sum(p._cache_size() for p in _TAPE_PROGRAMS.values()
                   if hasattr(p, "_cache_size"))

    # append a tail batch: zone maps extend, masks change as DATA — the
    # jitted programs must all be reused (no retrace across appends)
    progs0 = len(_TAPE_PROGRAMS)
    traces0 = _total_traces()
    n_append = max(table.n_records // 64, 1)
    src = make_forest_table(n_append, n_dup=1, seed=31)
    tail = {k: src.columns[k] for k in src.columns}
    tail["shard_0"] = ((table.n_records + np.arange(n_append))
                       // block).astype(np.float32)
    table.append({k: tail[k] for k in table.columns})
    prb.refresh()
    post = [prb.run_tape(tp) for tp in tapes]
    post_ok = all(np.array_equal(a, _oracle_bitmap(table, t))
                  for a, t in zip(post, trees))
    return {
        "rows": table.n_records,
        "queries": len(trees),
        "pruned_ms": round(pr["ms"], 3),
        "unpruned_ms": round(un["ms"], 3),
        "speedup": round(un["ms"] / pr["ms"], 2) if pr["ms"] else 0.0,
        "blocks_pruned": pr["blocks_pruned"],
        "blocks_touched_pruned": pr["blocks_touched"],
        "blocks_touched_unpruned": un["blocks_touched"],
        "tape_host_syncs_per_query": pr["syncs_per_query"],
        "host_fallbacks": pr["backend"].host_fallbacks,
        "programs_compiled_on_append": (len(_TAPE_PROGRAMS) - progs0
                                        + _total_traces() - traces0),
        "identical": bool(pr["identical"] and un["identical"] and post_ok),
    }


def _fragmented_tree():
    """String atoms whose dictionary hit sets fragment past MAX_CODE_RUNS:
    contains-LIKE (regex-shaped) and scattered IN — the shapes that fell
    back to the host gather before the dict-lookup kernel.  Numeric atoms
    carry ``value=None`` placeholders bound from the table's quantiles."""
    return Or([
        And([Atom("cover_0", "like", "%e%"),
             Atom("elevation_0", "lt", None), Atom("slope_0", "lt", None)]),
        And([Atom("cover_0", "in", ("aspen", "cedar", "hemlock", "maple",
                                    "pine", "willow")),
             Atom("h_dist_road_0", "lt", None)]),
        And([Atom("district_0", "in", tuple(f"district_{i:02d}"
                                            for i in (1, 4, 7, 11, 15,
                                                      19, 22))),
             Atom("hillshade_noon_0", "lt", None),
             Atom("aspect_0", "lt", None)]),
    ])


def bench_fragmented(table, repeats: int, block: int) -> dict:
    """Fragmented-strings workload: the dict-lookup kernel keeps regex /
    scattered-IN string atoms inside the ONE device program
    (host_fallbacks == 0); the pre-lookup reference path (rewrite
    disabled -> host gather per string atom) is timed alongside."""
    gs = {"elevation_0": 0.5, "slope_0": 0.6, "h_dist_road_0": 0.4,
          "hillshade_noon_0": 0.6, "aspect_0": 0.5}
    expr = _fragmented_tree()

    def bind(node):
        if isinstance(node, Atom):
            if node.value is None:
                g = gs[node.column]
                return Atom(node.column, "lt",
                            table.value_at_selectivity(node.column, g),
                            selectivity=g)
            return node
        return type(node)([bind(c) for c in node.children])

    tree = normalize(bind(expr))
    annotate_selectivities(tree, table)
    oracle = _oracle_bitmap(table, tree)
    n_strings = sum(1 for a in tree.atoms
                    if not np.issubdtype(table.columns[a.column].dtype,
                                         np.number))

    model = PerAtomCostModel()
    rtree = rewrite_string_atoms(tree, table)
    rplan = deepfish(rtree, model, total_records=table.n_records)
    tape = compile_tape(rplan)
    be = DeviceTapeBackend(table, block=block)
    t0 = time.perf_counter()
    be.run_tape(tape)
    cold_ms = (time.perf_counter() - t0) * 1e3
    be.device_dispatches = be.host_syncs = be.host_fallbacks = 0
    got = be.run_tape(tape)
    dispatches, syncs, fallbacks = (be.device_dispatches, be.host_syncs,
                                    be.host_fallbacks)
    tape_ms = _best_of(lambda: be.run_tape(tape), repeats) * 1e3

    # reference: the pre-lookup behavior (no code-space rewrite -> one
    # host gather round-trip per fragmented string atom)
    plan0 = deepfish(tree, model, total_records=table.n_records)
    tape0 = compile_tape(plan0)
    nr_be = DeviceTapeBackend(table, block=block)
    nr_be.run_tape(tape0)
    nr_be.host_syncs = nr_be.host_fallbacks = 0
    r_nr = nr_be.run_tape(tape0)
    nr_syncs, nr_fallbacks = nr_be.host_syncs, nr_be.host_fallbacks
    nr_ms = _best_of(lambda: nr_be.run_tape(tape0), repeats) * 1e3

    return {
        "atoms": tree.n,
        "string_atoms": n_strings,
        "tape_ops": len(tape.ops),
        "tape_ms": round(tape_ms, 3),
        "tape_cold_ms": round(cold_ms, 3),
        "norewrite_tape_ms": round(nr_ms, 3),
        "speedup": round(nr_ms / tape_ms, 2) if tape_ms else 0.0,
        "tape_device_dispatches": dispatches,
        "tape_host_syncs_per_query": syncs,
        "host_fallbacks": fallbacks,
        "norewrite_host_syncs": nr_syncs,
        "norewrite_host_fallbacks": nr_fallbacks,
        "identical": bool(np.array_equal(got, oracle)
                          and np.array_equal(r_nr, oracle)),
    }


def bench_sharded(rows: int, repeats: int, block: int) -> dict:
    """Sharded tape execution across the host-device mesh (child process).

    Runs ONLY under ``--sharded-child``: the parent spawns this file in a
    subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    because the device count is locked at first jax init and the forced
    split (8 single-threaded host devices) would distort every
    single-device section's timings.  Sweeps shard counts {1, 2, 8} over
    one query suite, asserting bit-identicality against the numpy oracle,
    ONE collective sync per query (one bundled sync per lockstep batch),
    zero retraces across an append, and a shard-local delta re-upload.

    The committed baseline section is produced at 500k rows: the forced
    host-platform split deadlocks in the XLA CPU collective rendezvous
    at 1M-row shard sizes on single-core hosts, and the gates are exact
    contract checks (not timing comparisons), so the smaller scale loses
    nothing.
    """
    import jax

    table = make_forest_table(rows, n_dup=2, seed=7)
    rng = np.random.default_rng(2)
    trees = [random_tree(table, 6, 3, rng) for _ in range(6)]
    oracles = [_oracle_bitmap(table, t) for t in trees]
    model = PerAtomCostModel()
    tapes = [compile_tape(deepfish(t, model,
                                   total_records=table.n_records))
             for t in trees]

    out = {"rows": table.n_records, "devices": jax.device_count(),
           "queries": len(trees), "block": block}
    identical, one_sync = True, True
    be8 = None
    for s in (1, 2, 8):
        be = ShardedTapeBackend(table, block=block, shards=s)
        for tp in tapes:
            be.run_tape(tp)                       # warm compiles + uploads
        s0 = be.host_syncs
        got = [be.run_tape(tp) for tp in tapes]
        one_sync &= (be.host_syncs - s0 == len(tapes))
        identical &= all(np.array_equal(a, b)
                         for a, b in zip(got, oracles))
        ms = _best_of(lambda: [be.run_tape(tp) for tp in tapes],
                      repeats) * 1e3
        out[f"shards{s}_ms"] = round(ms, 3)
        if s == 8:
            be8 = be

    def _total_traces():
        return sum(p._cache_size() for p in _TAPE_PROGRAMS.values()
                   if hasattr(p, "_cache_size"))

    # append a small tail: under 8 shards the dirty blocks land on ONE
    # shard and the jitted programs are all reused (masks are data)
    progs0, traces0 = len(_TAPE_PROGRAMS), _total_traces()
    src = make_forest_table(max(rows // 64, 1), n_dup=2, seed=31)
    table.append({k: src.columns[k] for k in table.columns})
    be8.refresh()
    out["delta_upload_shards"] = be8.delta_upload_shards
    post_ok = all(np.array_equal(be8.run_tape(tp),
                                 _oracle_bitmap(table, t))
                  for tp, t in zip(tapes, trees))
    out["programs_compiled_on_append"] = (len(_TAPE_PROGRAMS) - progs0
                                          + _total_traces() - traces0)

    # lockstep batch under sharding: ONE bundled collective sync
    sess = QuerySession(table, config=ExecConfig(
        planner="deepfish", engine="tape", block=block, batched=True,
        shards=8, persist_atom_cache=False))
    sess.execute(trees)                           # warm plans + columns
    s0 = sess._backend.host_syncs
    res = sess.execute(trees)
    out["lockstep_syncs_per_batch"] = res.backend.host_syncs - s0
    lockstep_ok = all(np.array_equal(b, _oracle_bitmap(table, t))
                      for b, t in zip(res.bitmaps, trees))

    out["one_sync_per_query"] = bool(one_sync)
    out["identical"] = bool(identical and post_ok and lockstep_ok)
    return out


def _run_sharded_child(args) -> dict:
    """Spawn this file with ``--sharded-child`` under the forced 8-device
    host platform and parse its RESULT line."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    cmd = [sys.executable, os.path.abspath(__file__), "--sharded-child",
           "--rows", str(args.rows), "--block", str(args.block),
           "--repeats", str(args.repeats)]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=3600)
    if proc.returncode != 0:
        raise SystemExit("FAIL: sharded child crashed:\n"
                         + proc.stderr[-3000:])
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("RESULT ")]
    if not lines:
        raise SystemExit("FAIL: sharded child produced no RESULT line:\n"
                         + proc.stdout[-2000:])
    return json.loads(lines[-1][len("RESULT "):])


def _workload(table, n_queries, n_templates, n_atoms, depth, seed):
    rng = np.random.default_rng(seed)
    pool = [random_tree(table, n_atoms, depth, rng)
            for _ in range(n_templates)]
    return [pool[rng.integers(n_templates)] for _ in range(n_queries)]


def bench_batch(table, queries, repeats: int, block: int) -> dict:
    """Per-step lockstep (jax) vs compiled tapes (tape) vs device-resident
    lockstep (tape_lockstep).  Cross-batch atom caching is disabled so each
    timed batch performs real kernel work; columns/plans/programs stay warm
    across repeats."""
    base = ExecConfig(planner="deepfish", engine="jax", block=block,
                      persist_atom_cache=False)
    sessions = {
        "jax": QuerySession(table, config=base),
        "tape": QuerySession(table, config=base.replace(engine="tape")),
        "tape_lockstep": QuerySession(table, config=base.replace(
            engine="tape", batched=True)),
    }
    out, results = {}, {}
    for name, sess in sessions.items():
        sess.execute(queries)                        # warm plans + columns
        be = sess._backend
        syncs0 = be.host_syncs if be is not None else 0
        r = sess.execute(queries)
        results[name] = r
        syncs = (be.host_syncs - syncs0) if be is not None else None
        best = r.wall_s
        for _ in range(max(repeats - 1, 0)):
            best = min(best, sess.execute(queries).wall_s)
        out[f"{name}_ms"] = round(best * 1e3, 3)
        out[f"{name}_host_syncs_per_batch"] = syncs
    out["queries"] = len(queries)
    out["speedup"] = round(out["jax_ms"] / out["tape_ms"], 2)
    out["identical"] = all(
        np.array_equal(a, b)
        for other in ("tape", "tape_lockstep")
        for a, b in zip(results["jax"].bitmaps, results[other].bitmaps))
    return out


def bench_differential(table, n_seeds: int, block: int) -> dict:
    """Bit-identical sweep: tape vs JaxBlockBackend across random trees."""
    mismatches = 0
    for seed in range(n_seeds):
        rng = np.random.default_rng(seed)
        tree = random_tree(table, int(rng.integers(4, 9)),
                           int(rng.integers(2, 4)), rng)
        base, _, _ = run_query(tree, table, config=ExecConfig(
            planner="deepfish", engine="jax"))
        got, _, be = run_query(tree, table, config=ExecConfig(
            planner="deepfish", engine="tape"))
        if not np.array_equal(base, got) or be.host_syncs != 1:
            mismatches += 1
    return {"seeds": n_seeds, "mismatches": mismatches,
            "identical": mismatches == 0}


def _drift_table(rows: int, seed: int = 11) -> Table:
    """Feedback-loop workload shape: a skewed low-cardinality numeric
    (crude eq estimates), a correlated pair (marginal estimates can never
    explain conditional truth), and a column whose distribution the append
    stream drifts."""
    rng = np.random.default_rng(seed)
    cat = rng.choice(7, size=rows,
                     p=[0.45, 0.2, 0.1, 0.1, 0.05, 0.05, 0.05]
                     ).astype(np.float64)
    x = rng.uniform(size=rows)
    y = np.clip(x + rng.normal(scale=0.05, size=rows), 0.0, 1.5)
    return Table({"cat": cat, "w": rng.uniform(size=rows), "x": x, "y": y,
                  "z": rng.normal(size=rows)})


def _drift_rows(n: int, round_idx: int, seed: int) -> dict:
    """Append batch: cat/w/x/y keep their distribution; z drifts upward."""
    rng = np.random.default_rng(seed)
    x = rng.uniform(size=n)
    return {
        "cat": rng.choice(7, size=n,
                          p=[0.45, 0.2, 0.1, 0.1, 0.05, 0.05, 0.05]
                          ).astype(np.float64),
        "w": rng.uniform(size=n),
        "x": x,
        "y": np.clip(x + rng.normal(scale=0.05, size=n), 0.0, 1.5),
        "z": rng.normal(loc=0.5 * (round_idx + 1), size=n),
    }


def bench_obs(table, queries, repeats: int, block: int) -> dict:
    """Observability overhead + zero-perturbation contract: the same warm
    lockstep tape batch with telemetry/trace off vs on (caller-owned
    registry + tracer).  Timed best-of so both arms see identical warm
    state; the contract half asserts bit-identical bitmaps and equal
    sync/dispatch counts — spans and gauges must never add device work."""
    from repro.columnar import Tracer
    from repro.runtime.telemetry import MetricsRegistry

    def run(telemetry, trace):
        cfg = ExecConfig(planner="deepfish", engine="tape", batched=True,
                         block=block, persist_atom_cache=False,
                         telemetry=telemetry, trace=trace)
        sess = QuerySession(table, config=cfg)
        sess.execute(queries)                    # warm plans + programs
        best, res = float("inf"), None
        for _ in range(max(repeats, 3)):
            r = sess.execute(queries)
            if res is None:
                res = r
            best = min(best, r.wall_s)
        return best, res

    off_s, r_off = run(False, False)
    reg, tr = MetricsRegistry(), Tracer()
    on_s, r_on = run(reg, tr)
    spans = tr.drain()
    out = {
        "queries": len(queries),
        "off_ms": round(off_s * 1e3, 3),
        "on_ms": round(on_s * 1e3, 3),
        "overhead_pct": round((on_s / off_s - 1.0) * 100.0, 2),
        "identical": bool(all(np.array_equal(a, b) for a, b in
                              zip(r_off.bitmaps, r_on.bitmaps))),
        "host_syncs_off": r_off.stats.host_syncs,
        "host_syncs_on": r_on.stats.host_syncs,
        "dispatches_off": r_off.stats.device_dispatches,
        "dispatches_on": r_on.stats.device_dispatches,
        "metrics_registered": len(reg.names()),
        "spans_per_batch": round(len(spans) / (max(repeats, 3) + 1), 1),
    }
    out["contracts_equal"] = bool(
        out["host_syncs_off"] == out["host_syncs_on"]
        and out["dispatches_off"] == out["dispatches_on"])
    return out


def bench_drift(rows: int, block: int, rounds: int = 5) -> dict:
    """Closed Q-Error feedback loop under a drifting workload.

    A lockstep tape session with ``feedback_absorb=True`` serves three
    fixed query shapes for ``rounds`` batches, interleaved with appends
    that drift one column's distribution:

    * ``cat == 0`` (skewed value, crude 1/n_distinct estimate): the
      realized count from round 1's bundled sync corrects the estimate,
      so the per-key Q-Error must collapse (``qerror_reduction``) and the
      replanned order must match the truth-annotated plan
      (``plan_cost_ratio_feedback``) where the naive estimate picked the
      wrong first atom (``plan_cost_ratio_naive`` > 1).
    * ``x < q33 AND y < q42`` with y correlated to x: marginal estimates
      are exact, so the canonical plan key never moves — but the realized
      conditional fraction stays ~2.4x the estimate, so the cached plan
      must be evicted-and-replanned (``drift_evictions``).
    * ``z < v`` while appends shift z: sketch extension + EWMA tracking
      keep serving bit-identical results as the data moves.

    Every batch must stay ONE bundled host sync, and every bitmap is
    checked against the numpy oracle on the current snapshot.
    """
    table = _drift_table(rows)
    model = PerAtomCostModel()
    # cut points sit mid-bucket (sel_step=0.05) so estimate jitter across
    # appends cannot flip the correlated query's canonical plan key — the
    # eviction-on-drift path needs genuine cache-hit servings to observe
    vx = float(np.quantile(table.columns["x"], 0.33))
    vy = float(np.quantile(table.columns["y"], 0.42))
    vz = float(np.quantile(table.columns["z"], 0.5))

    def make_queries():
        return [normalize(And([Atom("cat", "eq", 0.0),
                               Atom("w", "lt", 0.3)])),
                normalize(And([Atom("x", "lt", vx), Atom("y", "lt", vy)])),
                normalize(And([Atom("z", "lt", vz),
                               Atom("w", "lt", 0.7)]))]

    sess = QuerySession(table, config=ExecConfig(
        planner="deepfish", engine="tape", block=block, batched=True,
        feedback_absorb=True))
    eq_key = ("cat", "eq", 0.0)
    eq_qerrs, max_qerrs = [], []
    evictions = 0
    identical = True
    syncs_per_batch = []
    last = None
    for r in range(rounds):
        queries = make_queries()
        syncs0 = sess._backend.host_syncs if sess._backend is not None else 0
        res = sess.execute(queries)
        last = res
        syncs_per_batch.append(res.backend.host_syncs - syncs0)
        eq_qerrs.append(res.stats.atom_qerrors.get(eq_key, 1.0))
        max_qerrs.append(res.stats.max_qerror)
        evictions += res.stats.drift_evictions
        for q, bm in zip(queries, res.bitmaps):
            identical = identical and bool(
                np.array_equal(bm, _oracle_bitmap(table, q)))
        if r < rounds - 1:
            table.append(_drift_rows(max(rows // 16, 1), r, seed=100 + r))

    # plan quality on the eq query: cost the feedback-corrected order and
    # the naive (no-feedback) order under TRUTH selectivities
    truth = normalize(And([Atom("cat", "eq", 0.0), Atom("w", "lt", 0.3)]))
    annotate_selectivities(truth, table, empirical=True,
                           sample=min(table.n_records, 262_144))
    truth_plan = deepfish(truth, model, total_records=table.n_records)
    cost_truth = plan_cost(truth, truth_plan.order, model, table.n_records)
    key_to_aid = {atom_key(a): a.aid for a in truth.atoms}

    def cost_of(plan):
        order = [key_to_aid[atom_key(plan.tree.atoms[i])]
                 for i in plan.order]
        return plan_cost(truth, order, model, table.n_records)

    cost_feedback = cost_of(last.plans[0])
    naive = normalize(tree_copy(And([Atom("cat", "eq", 0.0),
                                     Atom("w", "lt", 0.3)])))
    annotate_selectivities(naive, table)      # analytic estimates only
    cost_naive = cost_of(deepfish(naive, model,
                                  total_records=table.n_records))

    return {
        "rows": table.n_records,
        "rounds": rounds,
        "queries_per_round": 3,
        "pre_max_qerror": round(max_qerrs[0], 4),
        "post_max_qerror": round(max_qerrs[-1], 4),
        "eq_qerror_pre": round(eq_qerrs[0], 4),
        "eq_qerror_post": round(eq_qerrs[-1], 4),
        "qerror_reduction": round(eq_qerrs[0] / max(eq_qerrs[-1], 1e-9), 2),
        "drift_evictions": evictions,
        "feedback_observations": last.stats.feedback_observations,
        "host_syncs_per_batch": max(syncs_per_batch),
        "plan_cost_ratio_feedback": round(cost_feedback / cost_truth, 4),
        "plan_cost_ratio_naive": round(cost_naive / cost_truth, 4),
        "identical": identical,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--atoms", type=int, default=16)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--templates", type=int, default=8)
    ap.add_argument("--block", type=int, default=8192)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--diff-seeds", type=int, default=6)
    ap.add_argument("--out", default="BENCH_device.json")
    ap.add_argument("--strings", dest="strings", action="store_true",
                    default=True,
                    help="run the dict-string workload (default: on)")
    ap.add_argument("--no-strings", dest="strings", action="store_false")
    ap.add_argument("--drift", dest="drift", action="store_true",
                    default=True,
                    help="run the Q-Error feedback-loop drift workload "
                         "(default: on)")
    ap.add_argument("--no-drift", dest="drift", action="store_false")
    ap.add_argument("--obs", dest="obs", action="store_true", default=True,
                    help="run the observability overhead section "
                         "(telemetry/trace on vs off; default: on)")
    ap.add_argument("--no-obs", dest="obs", action="store_false")
    ap.add_argument("--sharded", action="store_true",
                    help="also run the multi-device sharded-tape section "
                         "(spawns a subprocess with 8 forced host devices)")
    ap.add_argument("--sharded-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--smoke", action="store_true",
                    help="CI preset: small table, tiny batch")
    args = ap.parse_args()
    if args.smoke:
        # best-of-2 repeats: a single measurement of the small batch is too
        # noisy for the CI regression gate's speedup floors
        args.rows, args.batch, args.repeats = 50_000, 8, 2
        args.templates, args.diff_seeds = 2, 2

    if args.sharded_child:
        print("RESULT " + json.dumps(
            bench_sharded(args.rows, args.repeats, args.block)))
        return
    if args.sharded:
        import jax
        if jax.default_backend() != "cpu":
            # the child forces 8 *host* devices; on an accelerator host it
            # would also contend with this process for the chip
            raise SystemExit(
                f"--sharded simulates devices on the CPU and cannot run on "
                f"a {jax.default_backend()} host; run the sharded path on "
                "the chips with `python chip_smoke.py --chips 4`")

    table = make_forest_table(args.rows, n_dup=2, seed=7)
    rng = np.random.default_rng(0)
    tree = random_tree(table, args.atoms, args.depth, rng)
    annotate_selectivities(tree, table)

    print(f"table: {table.n_records} rows; single query: {args.atoms} atoms "
          f"depth {args.depth}")
    single = bench_single(table, tree, args.repeats, args.block)
    print(f"single: jax {single['jax_ms']:.1f} ms "
          f"({single['jax_kernel_invocations']} kernels, "
          f"{single['jax_host_syncs']} syncs)  vs  tape "
          f"{single['tape_ms']:.1f} ms "
          f"({single['tape_device_dispatches']} dispatch, "
          f"{single['tape_host_syncs_per_query']} sync; "
          f"cold {single['tape_cold_ms']:.0f} ms)  ->  "
          f"{single['speedup']:.2f}x  identical={single['identical']}")

    queries = _workload(table, args.batch, args.templates, 6, 3, seed=1)
    batch = bench_batch(table, queries, args.repeats, args.block)
    print(f"batch{batch['queries']}: jax {batch['jax_ms']:.1f} ms "
          f"({batch['jax_host_syncs_per_batch']} syncs)  vs  tape "
          f"{batch['tape_ms']:.1f} ms "
          f"({batch['tape_host_syncs_per_batch']} syncs)  vs  "
          f"tape-lockstep {batch['tape_lockstep_ms']:.1f} ms "
          f"({batch['tape_lockstep_host_syncs_per_batch']} sync)  ->  "
          f"{batch['speedup']:.2f}x  identical={batch['identical']}")

    selective = bench_selective(args.rows, args.repeats, args.block)
    print(f"selective: pruned {selective['pruned_ms']:.1f} ms  vs  "
          f"unpruned {selective['unpruned_ms']:.1f} ms  ->  "
          f"{selective['speedup']:.2f}x  "
          f"(pruned {selective['blocks_pruned']:.0f} blocks, touched "
          f"{selective['blocks_touched_pruned']:.0f} vs "
          f"{selective['blocks_touched_unpruned']:.0f}; "
          f"{selective['programs_compiled_on_append']} recompiles on "
          f"append)  identical={selective['identical']}")

    strings = None
    fragmented = None
    if args.strings:
        strings_table = make_forest_table(args.rows, n_dup=1, seed=13,
                                          strings=True)
        strings = bench_strings(strings_table, args.repeats, args.block)
        print(f"strings ({strings['string_atoms']}/{strings['atoms']} string "
              f"atoms): jax {strings['jax_ms']:.1f} ms  vs  tape "
              f"{strings['tape_ms']:.1f} ms "
              f"({strings['tape_device_dispatches']} dispatch, "
              f"{strings['tape_host_syncs_per_query']} sync, "
              f"{strings['host_fallbacks']} fallbacks)  vs  no-rewrite "
              f"{strings['norewrite_tape_ms']:.1f} ms "
              f"({strings['norewrite_host_syncs']} syncs, "
              f"{strings['norewrite_host_fallbacks']} fallbacks)  ->  "
              f"{strings['speedup']:.2f}x / "
              f"{strings['norewrite_speedup']:.2f}x "
              f"identical={strings['identical']}")

        fragmented = bench_fragmented(strings_table, args.repeats,
                                      args.block)
        print(f"fragmented ({fragmented['string_atoms']}/"
              f"{fragmented['atoms']} fragmented string atoms): tape "
              f"{fragmented['tape_ms']:.1f} ms "
              f"({fragmented['tape_device_dispatches']} dispatch, "
              f"{fragmented['tape_host_syncs_per_query']} sync, "
              f"{fragmented['host_fallbacks']} fallbacks)  vs  no-lookup "
              f"{fragmented['norewrite_tape_ms']:.1f} ms "
              f"({fragmented['norewrite_host_syncs']} syncs, "
              f"{fragmented['norewrite_host_fallbacks']} fallbacks)  ->  "
              f"{fragmented['speedup']:.2f}x  "
              f"identical={fragmented['identical']}")

    diff = bench_differential(table, args.diff_seeds, args.block)
    print(f"differential sweep: {diff['seeds']} seeds, "
          f"{diff['mismatches']} mismatches")

    sharded = None
    if args.sharded:
        sharded = _run_sharded_child(args)
        print(f"sharded ({sharded['devices']} devices, "
              f"{sharded['queries']} queries): 1 shard "
              f"{sharded['shards1_ms']:.1f} ms  vs  2 "
              f"{sharded['shards2_ms']:.1f} ms  vs  8 "
              f"{sharded['shards8_ms']:.1f} ms; "
              f"one_sync={sharded['one_sync_per_query']}, lockstep "
              f"{sharded['lockstep_syncs_per_batch']} sync/batch, "
              f"{sharded['programs_compiled_on_append']} recompiles on "
              f"append, delta on {sharded['delta_upload_shards']} "
              f"shard(s)  identical={sharded['identical']}")

    drift = None
    if args.drift:
        drift = bench_drift(args.rows, args.block)
        print(f"drift ({drift['rounds']} rounds x "
              f"{drift['queries_per_round']} queries): eq Q-Error "
              f"{drift['eq_qerror_pre']:.2f} -> {drift['eq_qerror_post']:.2f} "
              f"({drift['qerror_reduction']:.1f}x), "
              f"{drift['drift_evictions']} drift evictions, "
              f"{drift['host_syncs_per_batch']} sync/batch, plan cost "
              f"{drift['plan_cost_ratio_feedback']:.3f}x truth "
              f"(naive {drift['plan_cost_ratio_naive']:.3f}x)  "
              f"identical={drift['identical']}")

    obs = None
    if args.obs:
        obs = bench_obs(table, queries, args.repeats, args.block)
        print(f"obs ({obs['queries']} queries): off {obs['off_ms']:.1f} ms  "
              f"vs  on {obs['on_ms']:.1f} ms  ->  "
              f"{obs['overhead_pct']:+.1f}% overhead, "
              f"{obs['metrics_registered']} metrics, "
              f"{obs['spans_per_batch']:.0f} spans/batch, syncs "
              f"{obs['host_syncs_off']}->{obs['host_syncs_on']}  "
              f"identical={obs['identical']}")

    report = {
        "rows": table.n_records,
        "block": args.block,
        "single": single,
        "batch": batch,
        "selective": selective,
        "differential": diff,
        "acceptance": {
            "bit_identical": bool(single["identical"] and batch["identical"]
                                  and diff["identical"]
                                  and selective["identical"]
                                  and (strings is None
                                       or strings["identical"])
                                  and (fragmented is None
                                       or fragmented["identical"])),
            "single_speedup_ge_2x": bool(single["speedup"] >= 2.0),
            "tape_host_syncs_per_query": single["tape_host_syncs_per_query"],
            # the CPU-visible pruning win (lax.cond op skips) needs scans
            # big enough to dwarf the per-query fixed costs: the speedup
            # floor is asserted at full scale (the committed 1M baseline),
            # while the pruning/no-retrace contract holds at every size
            "selective_pruning_pays": bool(
                selective["blocks_pruned"] > 0
                and selective["programs_compiled_on_append"] == 0
                and (args.smoke or selective["speedup"] > 1.0)),
        },
    }
    if strings is not None:
        report["strings"] = strings
        report["acceptance"]["strings_one_device_program"] = bool(
            strings["tape_device_dispatches"] == 1
            and strings["tape_host_syncs_per_query"] == 1
            and strings["host_fallbacks"] == 0)
    if fragmented is not None:
        report["fragmented"] = fragmented
        report["acceptance"]["fragmented_one_device_program"] = bool(
            fragmented["tape_device_dispatches"] == 1
            and fragmented["tape_host_syncs_per_query"] == 1
            and fragmented["host_fallbacks"] == 0)
    if sharded is not None:
        report["sharded"] = sharded
        report["acceptance"]["sharded_one_collective_sync"] = bool(
            sharded["identical"]
            and sharded["one_sync_per_query"]
            and sharded["lockstep_syncs_per_batch"] == 1
            and sharded["programs_compiled_on_append"] == 0
            and sharded["delta_upload_shards"] == 1)
    if obs is not None:
        report["obs"] = obs
        # the ≤5% overhead ceiling is asserted at full scale (the committed
        # 1M baseline): at smoke scale the per-batch fixed costs dominate
        # and a few ms of gauge publishing reads as a large percentage
        report["acceptance"]["obs_zero_perturbation"] = bool(
            obs["identical"]
            and obs["contracts_equal"]
            and (args.smoke or obs["overhead_pct"] <= 5.0))
    if drift is not None:
        report["drift"] = drift
        report["acceptance"]["drift_feedback_loop_closes"] = bool(
            drift["identical"]
            and drift["drift_evictions"] > 0
            and drift["host_syncs_per_batch"] == 1
            and drift["qerror_reduction"] >= 1.5
            and drift["plan_cost_ratio_feedback"]
            <= drift["plan_cost_ratio_naive"] + 1e-9)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    if not report["acceptance"]["bit_identical"]:
        raise SystemExit("FAIL: tape engine diverged from JaxBlockBackend")
    if strings is not None and not report["acceptance"][
            "strings_one_device_program"]:
        raise SystemExit("FAIL: dict-string workload left the one-sync "
                         "device path")
    if fragmented is not None and not report["acceptance"][
            "fragmented_one_device_program"]:
        raise SystemExit("FAIL: fragmented-strings workload left the "
                         "one-sync device path")
    if not report["acceptance"]["selective_pruning_pays"]:
        raise SystemExit("FAIL: zone pruning did not prune/pay on the "
                         "selective workload (or appends retraced)")
    if sharded is not None and not report["acceptance"][
            "sharded_one_collective_sync"]:
        raise SystemExit("FAIL: sharded execution diverged, lost the "
                         "one-collective-sync contract, retraced on "
                         "append, or re-uploaded beyond the dirty shard")
    if obs is not None and not report["acceptance"]["obs_zero_perturbation"]:
        raise SystemExit("FAIL: telemetry/trace perturbed results, changed "
                         "sync/dispatch counts, or exceeded the 5% "
                         "overhead ceiling")
    if drift is not None and not report["acceptance"][
            "drift_feedback_loop_closes"]:
        raise SystemExit("FAIL: the Q-Error feedback loop did not close on "
                         "the drift workload (divergence, no evictions, "
                         "extra syncs, or no estimate correction)")


if __name__ == "__main__":
    main()
