"""Interpreter tests with stub clients — the reference's
generator/interpreter_test.clj patterns (SURVEY.md §4.4): op mix ratios,
monotone timestamps, crash→:info conversion, client open/close bookkeeping,
and a throughput floor."""

import threading
import time

import pytest

from jepsen_tpu import client as jclient
from jepsen_tpu import generator as gen
from jepsen_tpu import testkit
from jepsen_tpu.generator import NEMESIS, interpreter
from jepsen_tpu.utils import relative_time


def r(f="read", value=None):
    return {"f": f, "value": value}


def run(test):
    with relative_time():
        return interpreter.run(test)


def test_noop_client_runs_ops():
    t = testkit.noop_test(
        concurrency=2,
        generator=gen.clients(gen.limit(10, gen.repeat(r()))),
    )
    h = run(t)
    invokes = [o for o in h if o["type"] == "invoke"]
    oks = [o for o in h if o["type"] == "ok"]
    assert len(invokes) == 10
    assert len(oks) == 10


def test_atom_client_cas_register():
    client = testkit.atom_client()
    ops = [
        {"f": "write", "value": 1},
        {"f": "read"},
        {"f": "cas", "value": [1, 2]},
        {"f": "cas", "value": [1, 3]},
        {"f": "read"},
    ]
    t = testkit.noop_test(
        concurrency=1,
        client=client,
        generator=gen.clients(ops),
    )
    h = run(t)
    comps = [o for o in h if o["type"] != "invoke"]
    assert [c["type"] for c in comps] == ["ok", "ok", "ok", "fail", "ok"]
    reads = [c["value"] for c in comps if c["f"] == "read" and c["type"] == "ok"]
    assert reads == [1, 2]


def test_monotone_distinct_history_times():
    t = testkit.noop_test(
        concurrency=5,
        generator=gen.clients(gen.limit(200, gen.repeat(r()))),
    )
    h = run(t)
    ts = [o["time"] for o in h]
    assert all(b >= a for a, b in zip(ts, ts[1:]))
    assert all(isinstance(x, int) for x in ts)


class CrashingClient(jclient.Client):
    """Crashes every invoke — ops must become :info, processes must be
    recycled (interpreter.clj:142-157, 233-236)."""

    def invoke(self, test, op):
        raise RuntimeError("boom")


def test_crash_becomes_info_and_process_recycles():
    t = testkit.noop_test(
        concurrency=1,
        client=CrashingClient(),
        generator=gen.clients(gen.limit(3, gen.repeat(r()))),
    )
    h = run(t)
    infos = [o for o in h if o["type"] == "info"]
    assert len(infos) == 3
    assert all("indeterminate" in o["error"] for o in infos)
    procs = [o["process"] for o in h if o["type"] == "invoke"]
    assert len(set(procs)) == 3  # fresh pid per crash


def test_client_open_close_bookkeeping():
    client = testkit.atom_client()
    t = testkit.noop_test(
        concurrency=3,
        client=client,
        generator=gen.clients(gen.limit(9, gen.repeat(r()))),
    )
    run(t)
    # One open per worker (no crashes), one close per open on exit.
    assert client.stats["opens"] == 3
    assert client.stats["closes"] == client.stats["opens"]


def test_crashes_reopen_non_reusable_clients():
    class SometimesCrash(testkit.AtomClient):
        def invoke(self, test, op):
            if op["f"] == "crash":
                raise RuntimeError("boom")
            return super().invoke(test, op)

    client = SometimesCrash(testkit.AtomCell())
    t = testkit.noop_test(
        concurrency=1,
        client=client,
        generator=gen.clients([r("crash"), r("read"), r("crash"), r("read")]),
    )
    h = run(t)
    # 2 crashes -> 2 reopens beyond the initial one.
    assert client.stats["opens"] == 3
    reads = [o for o in h if o["f"] == "read" and o["type"] == "ok"]
    assert len(reads) == 2


class CountingNemesis:
    def __init__(self):
        self.ops = []

    def setup(self, test):
        return self

    def invoke(self, test, op):
        self.ops.append(op["f"])
        return {**op, "type": "info"}

    def teardown(self, test):
        pass

    def fs(self):
        return {"start", "stop"}


def test_nemesis_ops_route_to_nemesis_worker():
    nem = CountingNemesis()
    t = testkit.noop_test(
        concurrency=2,
        nemesis=nem,
        generator=gen.any_gen(
            gen.clients(gen.limit(5, gen.repeat(r()))),
            gen.nemesis([r("start"), r("stop")]),
        ),
    )
    h = run(t)
    assert nem.ops == ["start", "stop"]
    nem_ops = [o for o in h if o["process"] == NEMESIS]
    assert len(nem_ops) == 4  # 2 invokes + 2 infos


def test_sleep_and_log_excluded_from_history():
    t = testkit.noop_test(
        concurrency=1,
        generator=gen.clients([r("a"), gen.sleep(0.05), gen.log("hello"), r("b")]),
    )
    h = run(t)
    assert all(o["type"] in ("invoke", "ok") for o in h)
    assert [o["f"] for o in h if o["type"] == "invoke"] == ["a", "b"]


def test_time_limit_wall_clock():
    t = testkit.noop_test(
        concurrency=2,
        generator=gen.clients(gen.time_limit(0.3, gen.repeat(r()))),
    )
    start = time.monotonic()
    h = run(t)
    elapsed = time.monotonic() - start
    assert h
    assert elapsed < 5


@pytest.mark.perf
def test_throughput_floor():
    """The reference asserts >5,000 ops/s with stub clients
    (interpreter_test.clj:137-142).  One untimed warm-up run, then the
    best of three timed runs: a single wall-clock sample reads the
    other test workers sharing the CPU, not the interpreter."""
    n = 4000

    def timed_rate():
        t = testkit.noop_test(
            concurrency=10,
            generator=gen.clients(gen.limit(n, gen.repeat(r()))),
        )
        start = time.monotonic()
        h = run(t)
        elapsed = time.monotonic() - start
        assert len([o for o in h if o["type"] == "invoke"]) == n
        return n / elapsed

    timed_rate()  # warm-up
    rate = max(timed_rate() for _ in range(3))
    assert rate > 5000, f"only {rate:.0f} ops/s"


def test_generator_exception_tears_down_workers():
    class Bomb(gen.Gen):
        def op(self, test, ctx):
            raise RuntimeError("generator exploded")

    t = testkit.noop_test(concurrency=2, generator=Bomb())
    before = threading.active_count()
    with pytest.raises(RuntimeError):
        run(t)
    time.sleep(0.2)
    assert threading.active_count() <= before + 1
