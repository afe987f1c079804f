"""Bring-up check: the predicate engine's served path on a TPU.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four chips: the sharded tape only

Runs the paper's synthetic deployment (section 7.1, see
``repro.columnar.forest``): a Forest-style table of 5.8M rows and 144
attributes, made from ``--seed``, queried by a suite of 16 random
disjunctive trees of 12-16 atoms and depth 2-4.  Every phase goes through
the entry points a user calls:

(a) ``run_query`` on the ``tape`` and ``tape-pallas`` engines;
(b) a lockstep ``QuerySession`` batch on ``tape-pallas``;
(c) the served path: a ``StreamSession`` with its background drainer,
    re-submitting the suite around two 1% appends and a tombstone delete;
(d) fragmented string predicates (a LIKE and a scattered IN) on
    ``tape-pallas``, so the dictionary-lookup kernel runs in the compiled
    program.

With ``--chips 4`` only the sharded path runs: the suite through
``ExecConfig(engine="tape", shards=4)`` against the single-device ``tape``
engine, before and after a 1% append.

Every result bitmap must equal the plain full-scan numpy evaluation in
:func:`reference_bitmap`, which shares no code with the planner or the
engines.  Each phase prints one JSON line (rows, device bytes in use,
compile and wall seconds, checks passed); any failed check raises.  The
last line, printed only when every phase passed on a TPU, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Off a TPU the script exits non-zero before any phase runs.

The phases are importable functions, so ``tests/test_chip_smoke.py`` runs
them on the CPU at a small size with interpret-mode kernels.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.columnar import (ExecConfig, QuerySession,  # noqa: E402
                            StreamSession, make_forest_table, random_tree,
                            run_query)
from repro.columnar.persist import enable_compilation_cache  # noqa: E402
from repro.core.predicate import (And, Atom, Not, Or,  # noqa: E402
                                  PredicateTree, decode_column, normalize)

ROWS = 5_800_000          # the paper's 10x-replicated Forest table
N_DUP = 12                # 12 x (10 quantitative + 2 qualitative) = 144
N_QUERIES = 16
ATOMS = (12, 16)          # atoms per tree, inclusive
DEPTHS = (2, 4)           # tree depth, inclusive


class SmokeFailure(RuntimeError):
    """A phase produced a wrong answer or broke a serving contract."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# the reference: a plain full scan, independent of planner and engines
# ---------------------------------------------------------------------------

def _like(pattern: str) -> "re.Pattern":
    body = "".join(".*" if ch == "%" else "." if ch == "_" else re.escape(ch)
                   for ch in pattern)
    return re.compile(body, re.IGNORECASE)


def _atom_mask(atom: Atom, col: np.ndarray, uniques: dict) -> np.ndarray:
    """``atom`` on every row of ``col``.  String columns are evaluated on
    their distinct values and mapped back through the inverse index."""
    if col.dtype.kind not in "USO":
        return _eval_atom(atom, col)
    key = id(col)
    if key not in uniques:
        uniques[key] = (col, *np.unique(col, return_inverse=True))
    _, vals, inv = uniques[key]
    return _eval_atom(atom, vals)[inv]


def _eval_atom(atom: Atom, col: np.ndarray) -> np.ndarray:
    op, v = atom.op, atom.value
    if op in ("like", "not_like"):
        pat = _like(v)
        hit = np.array([pat.fullmatch(str(x)) is not None for x in col],
                       dtype=bool)
        return hit if op == "like" else ~hit
    if op in ("in", "not_in"):
        hit = np.isin(col, np.asarray(list(v)))
        return hit if op == "in" else ~hit
    return {"lt": np.less, "le": np.less_equal, "gt": np.greater,
            "ge": np.greater_equal, "eq": np.equal,
            "ne": np.not_equal}[op](col, v)


def _node_mask(node, columns, uniques) -> np.ndarray:
    if isinstance(node, Atom):
        return _atom_mask(node, columns[node.column], uniques)
    if isinstance(node, Not):
        return ~_node_mask(node.child, columns, uniques)
    parts = [_node_mask(c, columns, uniques) for c in node.children]
    return (np.logical_and if isinstance(node, And)
            else np.logical_or).reduce(parts)


def reference_bitmap(tree, columns, live=None) -> np.ndarray:
    """Packed ``u32`` words (record ``r`` = word ``r // 32``, bit
    ``r % 32``) of the rows of ``columns`` that satisfy ``tree`` and are
    ``live``."""
    root = tree.root if isinstance(tree, PredicateTree) else tree
    mask = _node_mask(root, columns, {})
    if live is not None:
        mask = mask & live
    raw = np.packbits(mask, bitorder="little")
    raw = np.concatenate([raw, np.zeros(-len(raw) % 4, dtype=np.uint8)])
    return raw.view("<u4")


def _same(got, want) -> bool:
    return np.array_equal(np.asarray(got, dtype=np.uint32), want)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def build_suite(table, n_queries: int = N_QUERIES, seed: int = 0,
                atoms=ATOMS, depths=DEPTHS):
    """Random disjunctive trees as the paper draws them (``queries.py``),
    with atom count and depth drawn per tree."""
    rng = np.random.default_rng(seed)
    suite = []
    for _ in range(n_queries):
        depth = int(rng.integers(depths[0], depths[1] + 1))
        n_atoms = int(rng.integers(max(atoms[0], 2 ** (depth - 1)),
                                   atoms[1] + 1))
        suite.append(random_tree(table, n_atoms, depth, rng))
    return suite


def sample_rows(table, n: int, seed: int) -> dict:
    """``n`` rows drawn from the table's own rows: an append batch with
    the deployment's distributions."""
    idx = np.random.default_rng(seed).integers(0, table.n_records, size=n)
    return {k: np.asarray(v)[idx] for k, v in table.columns.items()}


def string_queries(table):
    """Trees over a ``strings=True`` table whose string atoms fragment the
    sorted dictionary into more runs than range atoms can express: the
    rewrite turns them into code-membership atoms (``kernels.dict_lookup``).
    ``%e%`` over the cover species hits 5 runs; the IN hits 6 districts."""
    scattered = tuple(f"district_{i:02d}" for i in (0, 3, 7, 11, 15, 19))
    v = table.value_at_selectivity("elevation_0", 0.5)
    s = table.value_at_selectivity("slope_0", 0.3)
    return [
        normalize(And([Atom("cover_0", "like", "%e%"),
                       Or([Atom("elevation_0", "lt", v),
                           Atom("district_0", "in", scattered)])])),
        normalize(Or([Atom("district_0", "in", scattered),
                      And([Atom("slope_0", "lt", s),
                           Atom("cover_0", "like", "%E%")])])),
    ]


# ---------------------------------------------------------------------------
# measurement helpers
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, from its own
    monitoring events (registered once per process)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event in self.EVENTS:
            self.total += secs


def device_bytes():
    """Bytes in use on device 0, where the backend reports it."""
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else int(stats["bytes_in_use"])


def compiled_tape_text(be) -> str:
    """Compiled HLO of the whole-tape program of ``be``'s last tape, with
    the arguments the backend binds for it."""
    import jax.numpy as jnp
    tape = be.last_tape
    cols, values, lmasks, meta, _ = be._tape_bindings(tape)
    zmasks, decided = be._tape_zone_masks(tape)
    prog = be._tape_program(tape, tuple(meta), skip=decided)
    full = be.full()
    return prog.lower(tuple(cols), jnp.asarray(values, dtype=jnp.float32),
                      jnp.asarray(lmasks), zmasks, full.bits,
                      full.pops).compile().as_text()


class Phase:
    """Times one phase and collects its printed line.  The numpy reference
    is never timed: phases compute it before the ``with`` block, or inside
    :meth:`untimed` where it depends on the phase's own mutations."""

    def __init__(self, name: str, clock: CompileClock, rows: int):
        self.name, self.clock = name, clock
        self.line = {"phase": name, "rows": rows}
        self.checks = []
        self.paused = 0.0

    def __enter__(self):
        self.c0 = self.clock.total
        self.t0 = time.perf_counter()
        return self

    def passed(self, what: str) -> None:
        self.checks.append(what)

    @contextlib.contextmanager
    def untimed(self):
        """Leaves the enclosed stretch out of the phase's wall seconds."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.paused += time.perf_counter() - t0

    def __exit__(self, *exc):
        self.line["compile_s"] = self.clock.total - self.c0
        self.line["wall_s"] = time.perf_counter() - self.t0 - self.paused
        self.line["checks"] = self.checks
        return False


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_run_query(table, suite, clock) -> dict:
    """(a) ``run_query`` per tree on both whole-tape engines."""
    want = [reference_bitmap(t, table.columns) for t in suite]
    with Phase("a_run_query", clock, table.n_records) as ph:
        for engine in ("tape", "tape-pallas"):
            cfg = ExecConfig(planner="deepfish", engine=engine)
            be = None
            for i, tree in enumerate(suite):
                got, _, be = run_query(tree, table, config=cfg, backend=be)
                check(_same(got, want[i]), f"{engine} query {i} != numpy")
            check(be.host_fallbacks == 0, f"{engine} host fallbacks")
            ph.line[f"{engine}_interpret"] = be.interpret
            ph.passed(f"{engine}: {len(suite)}/{len(suite)} == numpy, "
                      "0 host fallbacks")
            ph.line[f"{engine}_device_bytes"] = device_bytes()
    # the tape-pallas backend is the last one; its AOT compile stays out
    # of the phase's compile and wall seconds
    ph.line["tpu_custom_call"] = "tpu_custom_call" in compiled_tape_text(be)
    return ph.line


def phase_lockstep(table, suite, clock) -> dict:
    """(b) one lockstep batch on ``tape-pallas``."""
    want = [reference_bitmap(t, table.columns) for t in suite]
    with Phase("b_lockstep", clock, table.n_records) as ph:
        sess = QuerySession(table, config=ExecConfig(
            planner="deepfish", engine="tape-pallas", batched=True))
        res = sess.execute(suite)
        ok = sum(_same(g, w) for g, w in zip(res.bitmaps, want))
        check(ok == len(suite), f"lockstep: {ok}/{len(suite)} == numpy")
        check(res.stats.host_fallbacks == 0, "lockstep host fallbacks")
        check(res.stats.lockstep_rounds > 0, "batch did not run lockstep")
        ph.passed(f"{ok}/{len(suite)} == numpy, 0 host fallbacks, "
                  f"{res.stats.host_syncs} host sync(s)")
        ph.line["tape-pallas_interpret"] = res.backend.interpret
        ph.line["device_bytes"] = device_bytes()
    return ph.line


def phase_served(table, suite, clock, seed: int = 0) -> dict:
    """(c) the served path: background drains around two 1% appends and a
    tombstone delete; every snapshot re-submits the suite."""
    rng = np.random.default_rng(seed + 1)
    live = np.ones(table.n_records, dtype=bool)
    with Phase("c_served", clock, table.n_records) as ph:
        stream = StreamSession(table, config=StreamSession.DEFAULT_CONFIG,
                               background=True)
        backends = {}
        try:
            for step in ("initial", "append", "delete", "append"):
                n = stream.table.n_records
                if step == "append":
                    stream.append(sample_rows(stream.table, n // 100,
                                              seed=int(rng.integers(1 << 30))))
                    live = np.concatenate(
                        [live, np.ones(stream.table.n_records - n, bool)])
                elif step == "delete":
                    dead = rng.choice(n, size=n // 100, replace=False)
                    stream.delete(dead)
                    live[dead] = False
                futs = [stream.submit(t) for t in suite]
                got = [f.result(timeout=900) for f in futs]
                with ph.untimed():
                    want = [reference_bitmap(t, stream.table.columns, live)
                            for t in suite]
                ok = sum(_same(g, w) for g, w in zip(got, want))
                check(ok == len(suite),
                      f"served {step}: {ok}/{len(suite)} == numpy")
                check(all(f.n_records == stream.table.n_records
                          for f in futs), f"served {step}: stale snapshot")
                be = stream.last_result.backend
                backends[id(be)] = be
                ph.passed(f"{step} ({stream.table.n_records} rows): "
                          f"{ok}/{len(suite)} == numpy")
            ph.line["device_bytes"] = device_bytes()
        finally:
            stream.close()
        st = stream.stats
        check(st.degraded_batches == st.quarantined_queries
              == st.retries == 0,
              f"degraded={st.degraded_batches} quarantined="
              f"{st.quarantined_queries} retries={st.retries}")
        fallbacks = sum(b.host_fallbacks for b in backends.values())
        check(fallbacks == 0, f"served host fallbacks: {fallbacks}")
        ph.passed(f"{st.batches} drains, 0 degraded / quarantined / "
                  "retried, 0 host fallbacks")
        ph.line["rows_final"] = stream.table.n_records
        ph.line["tape_interpret"] = any(b.interpret
                                        for b in backends.values())
    return ph.line


def phase_strings(table, clock) -> dict:
    """(d) fragmented string atoms through ``tape-pallas``."""
    queries = string_queries(table)
    want = [reference_bitmap(q, table.columns) for q in queries]
    with Phase("d_strings", clock, table.n_records) as ph:
        cfg = ExecConfig(planner="deepfish", engine="tape-pallas")
        be = None
        for i, q in enumerate(queries):
            got, _, be = run_query(q, table, config=cfg, backend=be)
            check(_same(got, want[i]), f"string query {i} != numpy")
            lookups = [a for a in be.last_tape.tree.atoms
                       if a.op == "in" and decode_column(a.column)]
            check(bool(lookups), f"string query {i} has no lookup atom")
        check(be.host_fallbacks == 0, "string host fallbacks")
        ph.passed(f"{len(queries)}/{len(queries)} == numpy with dictionary "
                  "lookups on device, 0 host fallbacks")
        ph.line["tape-pallas_interpret"] = be.interpret
        ph.line["device_bytes"] = device_bytes()
    ph.line["tpu_custom_call"] = "tpu_custom_call" in compiled_tape_text(be)
    return ph.line


def phase_sharded(table, suite, clock, shards: int = 4,
                  seed: int = 0) -> dict:
    """The sharded tape on ``shards`` devices against one device and the
    reference, before and after a 1% append."""
    single = QuerySession(table, config=ExecConfig(planner="deepfish",
                                                   engine="tape"))
    with Phase("sharded", clock, table.n_records) as ph:
        sharded = QuerySession(table, config=ExecConfig(
            planner="deepfish", engine="tape", shards=shards))
        be = None
        for step in ("initial", "append"):
            if step == "append":
                table.append(sample_rows(table, table.n_records // 100,
                                         seed=seed + 2))
            with ph.untimed():
                want = [reference_bitmap(t, table.columns) for t in suite]
            one = single.execute(suite)
            many = sharded.execute(suite)
            check(be is None or many.backend is be,
                  "append rebuilt the sharded backend")
            be = many.backend
            ph.line["tape_interpret"] = one.backend.interpret
            ph.line["sharded_interpret"] = be.interpret
            for name, res in (("single", one), ("sharded", many)):
                ok = sum(_same(g, w) for g, w in zip(res.bitmaps, want))
                check(ok == len(suite),
                      f"{name} {step}: {ok}/{len(suite)} == numpy")
                check(res.stats.host_fallbacks == 0,
                      f"{name} host fallbacks")
            same = all(np.array_equal(a, b)
                       for a, b in zip(one.bitmaps, many.bitmaps))
            check(same, f"sharded != single device after {step}")
            ph.passed(f"{step} ({table.n_records} rows): sharded == single "
                      f"device == numpy, {len(suite)}/{len(suite)}")
        ph.line["shards"] = be.shards
        ph.line["delta_upload_shards"] = be.delta_upload_shards
        ph.line["device_bytes"] = device_bytes()
    return ph.line


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def result_line(platform: str, kind: str, count: int) -> str:
    return json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded path on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compilation_cache()
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    clock = CompileClock()
    t0 = time.perf_counter()
    table = make_forest_table(ROWS, n_dup=N_DUP, seed=args.seed)
    suite = build_suite(table, seed=args.seed)
    print(json.dumps({"phase": "setup", "rows": table.n_records,
                      "attributes": len(table.columns),
                      "queries": len(suite),
                      "atoms": [t.n for t in suite],
                      "wall_s": time.perf_counter() - t0}), flush=True)
    lines = []
    if args.chips == 4:
        lines.append(phase_sharded(table, suite, clock, shards=4,
                                   seed=args.seed))
    else:
        for phase in (phase_run_query, phase_lockstep):
            lines.append(phase(table, suite, clock))
            print(json.dumps(lines[-1]), flush=True)
            gc.collect()            # drop the phase's device columns
        lines.append(phase_served(table, suite, clock, seed=args.seed))
        print(json.dumps(lines[-1]), flush=True)
        del table, suite
        gc.collect()
        strings = make_forest_table(ROWS, n_dup=1, seed=args.seed,
                                    strings=True)
        lines.append(phase_strings(strings, clock))
    print(json.dumps(lines[-1]), flush=True)
    for ln in lines:
        for key, val in ln.items():
            if key.endswith("_interpret"):
                check(val is False, f"{ln['phase']}: {key} is {val}")
            if key == "tpu_custom_call":
                check(val is True, f"{ln['phase']}: no tpu_custom_call")
    print(result_line(devices[0].platform, devices[0].device_kind,
                      len(devices)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
