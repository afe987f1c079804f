"""Spans and counters of the served path: request queueing, lock waits,
mutations, the drainer's waits, compiles, the dispatch split — and that
none of it changes an answer or records anything with tracing off."""
import glob
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.columnar import (ExecConfig, StreamSession, Table, Tracer,
                            make_forest_table, random_tree)
from repro.columnar.device import ZONED_ATOM_BOOKKEEPING, DeviceTapeBackend
from repro.columnar.drainer import DrainPolicy
from repro.columnar.trace import COMPILE_EVENT, tracer
from repro.core import Atom


def _stream(table, trace, **kw):
    cfg = ExecConfig(planner="deepfish", engine="tape", batched=True,
                     telemetry=False, trace=trace)
    return StreamSession(table, config=cfg, **kw)


def _trees(table, k, seed):
    rng = np.random.default_rng(seed)
    return [random_tree(table, 4, 2, rng) for _ in range(k)]


def _rows_like(table, n, seed):
    src = make_forest_table(n, n_dup=1, seed=seed)
    return {name: src.columns[name] for name in table.columns}


def test_served_path_spans_and_parents():
    t = make_forest_table(4000, n_dup=1, seed=3)
    tr = Tracer()
    ss = _stream(t, tr, background=True, policy=DrainPolicy(20.0, 2.0))
    with tr.span("caller"):
        ss.append(_rows_like(t, 512, seed=4))
        ss.delete(np.arange(0, 64))
    futs = [ss.submit(q) for q in _trees(t, 3, seed=5)]
    for f in futs:
        f.result(timeout=60)
    ss.close()
    spans = tr.drain()
    by_seq = {s.seq: s for s in spans}

    def parent(s):
        return by_seq[s.parent_seq].name if s.parent_seq is not None \
            else None

    caller = next(s for s in spans if s.name == "caller")
    for name in ("stream.append", "stream.delete"):
        (mut,) = [s for s in spans if s.name == name]
        assert parent(mut) == "caller" and mut.depth == 1
        assert caller.t0 <= mut.t0 <= caller.t0 + caller.dur_ms / 1e3
    queued = [s for s in spans if s.name == "stream.queued"]
    assert sorted(s.attrs["id"] for s in queued) == [f.id for f in futs]
    assert all(s.attrs["lane"] == "bulk" for s in queued)
    drains = [s for s in spans if s.name == "stream.drain"
              and s.thread == "stream-drainer"]
    assert drains
    lo = min(d.attrs["ids"][0] for d in drains)
    hi = max(d.attrs["ids"][1] for d in drains)
    assert (lo, hi) == (futs[0].id, futs[-1].id)
    for name in ("stream.queued", "stream.lock_wait", "stream.drain",
                 "stream.resolve"):
        on_drainer = [s for s in spans if s.name == name
                      and s.thread == "stream-drainer"]
        assert on_drainer, name
        assert all(parent(s) == "drainer.deadline_drain"
                   for s in on_drainer), name
    assert all(parent(s) == "stream.drain"
               for s in spans if s.name == "batch.execute")
    names = {s.name for s in spans}
    assert {"drainer.idle", "drainer.deadline_wait"} <= names
    for s in spans:
        if s.name.startswith("drainer."):
            assert s.depth == 0 and s.thread == "stream-drainer"
    # a queued request waits out the bulk deadline before its drain
    assert max(s.dur_ms for s in queued) >= 20.0 * 0.9


class _SignallingLock:
    """The session's drain lock, announcing each waiter on one thread."""

    def __init__(self, inner, thread_name, waiting):
        self._inner = inner
        self._thread = thread_name
        self._waiting = waiting

    def __enter__(self):
        if threading.current_thread().name == self._thread:
            self._waiting.set()
        return self._inner.__enter__()

    def __exit__(self, *exc):
        return self._inner.__exit__(*exc)


def test_lock_wait_covers_a_held_append():
    t = make_forest_table(3000, n_dup=1, seed=3)
    tr = Tracer()
    ss = _stream(t, tr)
    held, release, waiting = (threading.Event(), threading.Event(),
                              threading.Event())
    append = t.append

    def held_append(rows):
        held.set()
        assert release.wait(30)
        return append(rows)

    t.append = held_append
    ss._drain_lock = _SignallingLock(ss._drain_lock, "drain", waiting)
    fut = ss.submit(_trees(t, 1, seed=6)[0])
    mut = threading.Thread(target=ss.append, args=(_rows_like(t, 256, 8),),
                           name="append")
    mut.start()
    assert held.wait(30)
    drain = threading.Thread(target=ss.drain, name="drain")
    drain.start()
    assert waiting.wait(30)         # the drain now waits for the lock
    delay_s = 0.25
    release.wait(delay_s)           # the planted delay
    release.set()
    mut.join(60)
    drain.join(60)
    assert not mut.is_alive() and not drain.is_alive()
    assert fut.done()
    ss.close()
    spans = tr.drain()
    (wait,) = [s for s in spans if s.name == "stream.lock_wait"
               and s.thread == "drain"]
    assert wait.dur_ms >= delay_s * 1e3
    (app,) = [s for s in spans if s.name == "stream.append"]
    assert app.dur_ms >= delay_s * 1e3
    # the drain took the lock only after the append released it
    assert wait.t0 + wait.dur_ms / 1e3 >= app.t0 + app.dur_ms / 1e3 - 1e-3


def test_compile_spans_match_jax_compile_events():
    # a block size no other test uses, so these drains compile programs
    t = make_forest_table(5000, n_dup=1, seed=9)
    tr = Tracer()
    cfg = ExecConfig(planner="deepfish", engine="tape", batched=True,
                     telemetry=False, trace=tr, block=1184)
    ss = StreamSession(t, config=cfg)
    counting = threading.Event()
    events = []

    def listener(event, secs, **kw):
        if event == COMPILE_EVENT and counting.is_set():
            events.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listener)
    counting.set()
    try:
        for seed in (11, 12):
            for q in _trees(t, 2, seed=seed):
                ss.submit(q)
            ss.drain()
    finally:
        counting.clear()
    ss.close()
    spans = [s for s in tr.drain() if s.name == "jax.compile"]
    assert events, "the drains compiled nothing"
    assert len(spans) == len(events)
    assert sorted(s.attrs["fun_name"] for s in spans) == sorted(events)
    assert all(s.attrs["fun_name"] and s.parent_seq is not None
               for s in spans)


def test_compile_span_goes_to_the_open_span_only():
    tr = Tracer()
    n_global = len(tracer())

    def fresh(k):
        return jax.jit(lambda x: x * k + 1)(jnp.arange(7 + k))

    fresh(3)                                # no span open: recorded nowhere
    assert len(tr) == 0
    with tr.span("outer"):
        fresh(5)
    spans = tr.drain()
    outer = next(s for s in spans if s.name == "outer")
    comp = [s for s in spans if s.name == "jax.compile"]
    assert comp and all(s.parent_seq == outer.seq for s in comp)
    assert all(s.attrs["fun_name"] for s in comp)
    assert all(outer.t0 - 1e-3 <= s.t0 for s in comp)
    assert len(tracer()) == n_global


def test_record_nests_under_the_open_span():
    tr = Tracer()
    with tr.span("outer"):
        tr.record("past", 1.0, 1.5, id=7)
    tr.record("top", 2.0, 2.25)
    spans = {s.name: s for s in tr.drain()}
    assert spans["past"].parent_seq == spans["outer"].seq
    assert spans["past"].depth == 1 and spans["past"].attrs == {"id": 7}
    assert spans["past"].dur_ms == pytest.approx(500.0)
    assert spans["top"].parent_seq is None and spans["top"].depth == 0
    off = Tracer(enabled=False)
    off.record("x", 0.0, 1.0)
    assert len(off) == 0


def _zoned_table(n=8192):
    x = np.arange(n, dtype=np.float64)
    return Table({"x": x, "y": x[::-1].copy()})


def test_dispatch_split_counters():
    t = _zoned_table()
    be = DeviceTapeBackend(t, block=256)
    atom = Atom("x", "lt", 3000.5, selectivity=0.37)
    assert be._zone_mask([atom]) is not None     # zone-pruned
    full = be.full()
    k0, b0 = be.kernel_launches, be.bookkeeping_launches
    s0 = (be.kernel_host_s, be.bookkeeping_host_s, be.zone_host_s)
    out = be.apply_atom(atom, full)
    assert be.kernel_launches - k0 == 1
    assert be.bookkeeping_launches - b0 == ZONED_ATOM_BOOKKEEPING
    assert be.kernel_host_s > s0[0] and be.bookkeeping_host_s > s0[1]
    assert be.zone_host_s > s0[2]
    # set ops, a multi-set atom and an intersect-many: each dispatch is
    # exactly one kernel or set-op launch
    other = be.apply_atom_multi(Atom("y", "ge", 100.0), [out, full])
    be.inter_multi(out, other)
    be.union(out, other[0])
    be.materialize([out])
    assert be.setop_launches > 0 and be.setop_host_s > 0
    assert be.kernel_launches + be.setop_launches == be.device_dispatches
    # an append's delta splice counts its OR as a set-op launch, but
    # adds no host seconds outside the executors' calls
    t.append({"x": np.arange(100, dtype=np.float64),
              "y": np.arange(100, dtype=np.float64)})
    be.refresh()
    host = be.setop_host_s
    be.extend_set(out, 8192, np.ones(100, dtype=bool))
    assert be.setop_host_s == host
    assert be.kernel_launches + be.setop_launches == be.device_dispatches


def test_whole_tape_counts_one_kernel_launch():
    from repro.columnar import QuerySession
    t = _zoned_table()
    sess = QuerySession(t, config=ExecConfig(
        planner="deepfish", engine="tape", telemetry=False, trace=False,
        block=256))
    q = (Atom("x", "lt", 3000.5, selectivity=0.37)
         | Atom("y", "lt", 100.0, selectivity=0.01))
    res = sess.execute([q])
    be = res.backend
    assert be.kernel_launches == be.device_dispatches == 1
    assert be.setop_launches == 0
    assert be.kernel_host_s > 0 and be.bookkeeping_launches > 0


def test_answers_identical_and_trace_off_records_nothing():
    t_on = make_forest_table(4000, n_dup=1, seed=13)
    t_off = make_forest_table(4000, n_dup=1, seed=13)
    queries = _trees(t_on, 4, seed=14)
    watcher = Tracer()
    n_global = len(tracer())
    answers = []
    for t, trace in ((t_on, Tracer()), (t_off, False)):
        ss = _stream(t, trace, background=True,
                     policy=DrainPolicy(10.0, 1.0))
        ss.append(_rows_like(t, 300, seed=15))
        futs = [ss.submit(q) for q in queries]
        answers.append([f.result(timeout=60) for f in futs])
        ss.delete(np.arange(5, 40))
        answers.append([ss.submit(q).result(timeout=60) for q in queries])
        ss.close()
        if trace is False:
            assert ss.tracer is None
    for a, b in zip(answers[:2], answers[2:]):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    assert len(watcher) == 0 and len(tracer()) == n_global


def test_profiler_annotations_share_the_span_clock(tmp_path):
    from jax.profiler import ProfileData
    t = make_forest_table(3000, n_dup=1, seed=17)
    tr = Tracer(profiler=True)
    ss = _stream(t, tr)
    ss.submit(_trees(t, 1, seed=18)[0])
    ss.drain()                              # compile outside the capture
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for seed in range(19, 23):
            ss.submit(_trees(t, 1, seed=seed)[0])
            ss.drain()
    finally:
        jax.profiler.stop_trace()
    ss.close()
    spans = sorted((s for s in tr.drain() if s.name == "stream.drain"),
                   key=lambda s: s.t0)[-4:]
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    starts = sorted(ev.start_ns for plane in ProfileData.from_file(path).planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for ev in line.events
                    if ev.name == "stream.drain")
    assert len(starts) == len(spans) == 4
    offsets = [ns * 1e-9 - s.t0 for ns, s in zip(starts, spans)]
    assert max(offsets) - min(offsets) < 1e-3
