"""The refresh stream on the window's clock, the replayed warm-up and its
wait for compiles, on the CPU with stand-ins for the system."""
import json
import math
import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402
from bench.system import ProgramSystem  # noqa: E402
from bench.traffic import Mix, Mutations, RefreshThread  # noqa: E402

SEED = 2**35 + 17


class _Data:
    def __init__(self):
        self.mutations = []

    def mutation(self, name, rng):
        return "append", name


class _System:
    def __init__(self, mutate_s):
        self.mutate_s = mutate_s
        self.calls = 0

    def mutate(self, kind, payload):
        self.calls += 1
        time.sleep(self.mutate_s)


def _window(period_s, seconds, mutate_s, setup_pairs=0):
    system = _System(mutate_s)
    muts = Mutations()
    refresh = RefreshThread({"period_s": period_s,
                             "mutations": ["rf1", "rf2"]},
                            _Data(), system, muts, np.random.default_rng(0))
    for _ in range(setup_pairs):
        refresh.pair()
    t0 = time.perf_counter()
    refresh.start(t0, t0 + seconds)
    time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    refresh.join()
    assert not refresh.thread.is_alive()
    assert refresh.error is None
    return refresh, system, muts


@pytest.mark.parametrize("period_s,seconds", [(0.2, 1.0), (0.3, 1.2),
                                              (0.25, 0.9)])
def test_a_fast_refresh_makes_every_pair_due_in_the_window(period_s,
                                                           seconds):
    refresh, system, muts = _window(period_s, seconds, 0.001,
                                    setup_pairs=2)
    due = math.ceil((seconds - period_s / 2) / period_s)
    assert len(refresh.late) == len(refresh.took) == due
    assert max(refresh.late) < period_s / 2
    # two mutations a pair, the set-up's two pairs besides the window's
    assert muts.done == muts.started == system.calls == 2 * (due + 2)


def test_a_slow_refresh_still_makes_every_due_pair_late():
    period_s, seconds = 0.2, 1.0
    refresh, system, _ = _window(period_s, seconds, 0.15)
    due = math.ceil((seconds - period_s / 2) / period_s)
    assert len(refresh.late) == due
    # each pair takes 0.3 s against a period of 0.2 s: it starts when the
    # one before ends, later by 0.1 s more each time
    assert refresh.late[-1] >= (due - 1) * 0.1 - 0.02
    assert system.calls == 2 * due


def test_a_stopped_refresh_starts_no_other_pair():
    refresh = RefreshThread({"period_s": 0.1, "mutations": ["rf1"]},
                            _Data(), _System(0.0), Mutations(),
                            np.random.default_rng(0))
    refresh.stop()                      # never started: nothing to end
    assert refresh.thread.ident is None
    refresh = RefreshThread({"period_s": 0.1, "mutations": ["rf1"]},
                            _Data(), _System(0.0), Mutations(),
                            np.random.default_rng(0))
    t0 = time.perf_counter()
    refresh.start(t0, t0 + 60.0)
    time.sleep(0.22)
    refresh.stop()
    assert not refresh.thread.is_alive()
    assert len(refresh.late) == 2       # due at 0.05 s and 0.15 s


class _SlowRefreshSystem(ProgramSystem):
    def mutate(self, kind, payload):
        time.sleep(0.4)
        super().mutate(kind, payload)


def test_a_whole_run_logs_the_pairs_and_their_lateness():
    lines = []
    out = harness.run_cell(
        "tpch-throughput", SEED, 1.0, False, t_process=0.0,
        config_overrides={"scale_factor": 0.002},
        traffic_overrides={"warmup_replay": {"q19": 2},
                           "check_sample": 8,
                           "warmup_compile_wait_s": 1.0,
                           "refresh": {"period_s": 0.3,
                                       "mutations": ["rf1", "rf2"]}},
        make_system=_SlowRefreshSystem, late_s=15.0,
        log=lambda s, **k: lines.append(json.loads(s)))
    assert out["correct"], out["checks"]
    window = next(d for d in lines if d["phase"] == "window")
    due = math.ceil((1.0 - 0.15) / 0.3)
    assert window["refresh_pairs"] == due
    assert window["refresh_late_p95_ms"] > 100
    # the set-up's pair and the window's, two mutations each
    assert window["mutations"] == 2 * (1 + due)
    assert 0 < window["compile_wait_s"] <= 1.0 + 0.5


class _Family:
    def __init__(self, name):
        self.name = name

    def __call__(self, rng):
        return (self.name, int(rng.integers(1 << 30)))


class _Families:
    def family(self, name, params, rng):
        return _Family(name)


MIX = {"mix": [{"family": "a"}, {"family": "b"}], "order": "cycle",
       "warmup_replay": {"a": 3, "b": 3}}


def test_a_replayed_mix_knows_the_window_s_queries_ahead():
    mix = Mix(MIX, _Families(), np.random.default_rng(SEED))
    ahead = [[mix.spec(k, i) for i in range(3)] for k in range(2)]
    rng = np.random.default_rng(1)
    # two streams, each walking the families in turn, in any interleaving
    got = [mix.next(rng, p) for p in (0, 0, 1, 2, 1, 3)]
    assert got == [ahead[0][0], ahead[0][1], ahead[1][0], ahead[0][2],
                   ahead[1][1], ahead[1][2]]
    mix.rewind()
    assert mix.next(rng, 1) == ahead[1][0]
    # the same seed draws the same queries; another seed others
    again = Mix(MIX, _Families(), np.random.default_rng(SEED))
    assert again.spec(1, 2) == ahead[1][2]
    other = Mix(MIX, _Families(), np.random.default_rng(SEED + 1))
    assert other.spec(1, 2) != ahead[1][2]


class _Recorder:
    def __init__(self):
        self.submitted = []

    def submit(self, spec):
        self.submitted.append(spec)
        return _Done()

    def mutate(self, kind, payload):
        self.submitted.append(("mutate", payload))


class _Done:
    def done(self):
        return True

    def result(self):
        return None


def test_the_warm_up_applies_its_pairs_then_replays_the_window_s_queries():
    system = _Recorder()
    mix = Mix(MIX, _Families(), np.random.default_rng(SEED))
    muts = Mutations()
    refresh = RefreshThread({"period_s": 1.0, "mutations": ["rf1", "rf2"]},
                            _Data(), system, muts, np.random.default_rng(0))
    # only the named family is replayed
    traffic = {**MIX, "warmup_replay": {"b": 3}, "loop": "closed",
               "streams": 2, "warmup_requests": 0}
    harness.warm_up(system, mix, traffic, np.random.default_rng(2), muts,
                    5.0, refresh)
    assert system.submitted[:2] == [("mutate", "rf1"), ("mutate", "rf2")]
    assert system.submitted[2:] == [mix.spec(1, i) for i in range(3)]
    assert muts.done == 2
    # the window starts at each family's first query
    assert mix.next(np.random.default_rng(3), 0) == mix.spec(0, 0)


def _compile_forever(stop):
    import jax
    i = 0
    while not stop.is_set():
        i += 1
        jax.jit(lambda x, i=i: x * i + i).lower(1.0).compile()


def test_the_compile_wait_ends_at_its_cap_when_compiles_never_stop():
    compiles = harness.CompileCounter()
    stop = threading.Event()
    worker = threading.Thread(target=_compile_forever, args=(stop,))
    worker.start()
    try:
        while compiles.count == 0:
            time.sleep(0.01)
        waited = harness.wait_for_compiles(compiles, cap_s=1.0,
                                           quiet_s=0.5)
        assert 1.0 <= waited < 2.0
    finally:
        stop.set()
        worker.join(timeout=60)
    assert not worker.is_alive()


class _Compiles:
    """Stands in for ``CompileCounter``: one compile running, or the last
    one started or ended at ``last``."""

    def __init__(self, last=None):
        self.last = last

    def quiet_since(self):
        return self.last


def test_the_compile_wait_ends_a_quiet_period_after_the_last_compile():
    # a compile still running holds the wait to its cap
    assert 0.3 <= harness.wait_for_compiles(_Compiles(), cap_s=0.3,
                                            quiet_s=0.1) < 1.0
    # the last compile ended just now: the wait is the quiet period
    waited = harness.wait_for_compiles(_Compiles(time.perf_counter()),
                                       cap_s=5.0, quiet_s=0.3)
    assert 0.3 <= waited < 1.0
    # none for long, or none ever: the quiet period counts from the call
    for last in (time.perf_counter() - 60.0, -math.inf):
        waited = harness.wait_for_compiles(_Compiles(last), cap_s=5.0,
                                           quiet_s=0.3)
        assert 0.3 <= waited < 1.0


def test_the_compile_counter_hears_a_compile_start_before_its_end():
    compiles = harness.CompileCounter()
    compiles._on_start(harness.COMPILE_EVENT, 0.0)
    assert compiles.quiet_since() is None
    compiles._on(harness.COMPILE_EVENT, 0.0)
    assert compiles.count >= 1
