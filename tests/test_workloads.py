"""Workload-bundle tests: independent keyspace sharding + each bundle's
generator and checker on literal/simulated histories."""

import random

import pytest

from jepsen_tpu import generator as gen
from jepsen_tpu import history as h
from jepsen_tpu import independent, testkit
from jepsen_tpu.generator import testing as gt
from jepsen_tpu.workloads import adya, append, bank, causal, linearizable_register, long_fork, sets, wr


def ok(f, value, process=0, time=0, index=0):
    return {"type": "ok", "f": f, "value": value, "process": process, "time": time, "index": index}


def invoke(f, value, process=0, time=0, index=0):
    return {"type": "invoke", "f": f, "value": value, "process": process, "time": time, "index": index}


def pairs(*ops):
    """Interleave invoke/ok pairs sequentially with indices/times."""
    out = []
    for i, (f, inv_v, ok_v, proc) in enumerate(ops):
        out.append({"type": "invoke", "f": f, "value": inv_v, "process": proc,
                    "time": 2 * i, "index": 2 * i})
        out.append({"type": "ok", "f": f, "value": ok_v, "process": proc,
                    "time": 2 * i + 1, "index": 2 * i + 1})
    return out


# ---------------------------------------------------------------------------
# independent
# ---------------------------------------------------------------------------


def test_tuple_roundtrip():
    t = independent.tuple_("k1", 42)
    assert independent.is_tuple(t)
    assert independent.tuple_key(t) == "k1"
    assert independent.tuple_value(t) == 42


def test_sequential_generator_tags_values():
    g = independent.sequential_generator(
        ["a", "b"], lambda k: [{"f": "read", "value": None}]
    )
    h = gt.quick({"concurrency": 2}, gen.clients(g))
    keys = [independent.tuple_key(o["value"]) for o in h]
    assert keys == ["a", "b"]


def test_concurrent_generator_shards_threads():
    g = independent.concurrent_generator(
        2, range(6), lambda k: gen.limit(4, gen.repeat({"f": "read"}))
    )
    h = gt.perfect({"concurrency": 4}, gen.clients(g))
    invs = [o for o in h if o["type"] == "invoke"]
    assert len(invs) == 24  # 6 keys × 4 ops
    # Threads 0-1 form group 0, threads 2-3 group 1; a key never spans groups.
    key_groups = {}
    for o in invs:
        k = independent.tuple_key(o["value"])
        g_ = o["process"] % 4 // 2
        key_groups.setdefault(k, set()).add(g_)
    assert all(len(gs) == 1 for gs in key_groups.values())


def test_subhistory_and_keys():
    h = [
        invoke("read", independent.tuple_("a", None), 0),
        ok("read", independent.tuple_("a", 1), 0),
        invoke("read", independent.tuple_("b", None), 1),
        {"type": "info", "f": "start", "value": None, "process": "nemesis"},
    ]
    assert independent.history_keys(h) == ["a", "b"]
    sub = independent.subhistory("a", h)
    assert [o.get("value") for o in sub] == [None, 1, None]  # nemesis op kept


def test_independent_checker_merges_validity():
    from jepsen_tpu.checker import Checker

    class ValueIsOne(Checker):
        def check(self, test, history, opts):
            vals = [o["value"] for o in history if o["type"] == "ok"]
            return {"valid?": all(v == 1 for v in vals)}

    hist = pairs(
        ("read", independent.tuple_("a", None), independent.tuple_("a", 1), 0),
        ("read", independent.tuple_("b", None), independent.tuple_("b", 2), 1),
    )
    res = independent.checker(ValueIsOne()).check({"name": "t"}, hist, {})
    assert res["valid?"] is False
    assert res["failures"] == ["b"]
    assert res["results"]["a"]["valid?"] is True


def _unkeyed(rng):
    """A nemesis-style op: no key tag, sometimes no ``value`` at all."""
    return rng.choice([
        {"type": "info", "f": "start", "value": None, "process": "nemesis"},
        {"type": "info", "f": "kill", "value": ["n1", "n2", "n3"],
         "process": "nemesis"},
        {"type": "info", "f": "stop", "process": "nemesis"},
        {"type": "invoke", "f": "read", "value": 3, "process": 9},
    ])


def _random_keyed_history(seed):
    """Seeded keyed history over int and str keys, with unkeyed ops before
    the first keyed op, between keyed ops and after the last one."""
    rng = random.Random(seed)
    keys = rng.sample([0, 1, 2, 7, "a", "b", "zz"], rng.randint(1, 5))
    out = [_unkeyed(rng) for _ in range(rng.randint(0, 3))]
    for _ in range(rng.randint(5, 80)):
        if rng.random() < 0.2:
            out.append(_unkeyed(rng))
            continue
        k, p = rng.choice(keys), rng.randint(0, 4)
        out.append({"type": "invoke", "f": "write", "process": p,
                    "value": independent.tuple_(k, rng.randint(0, 4))})
        out.append({"type": rng.choice(["ok", "info"]), "f": "write",
                    "process": p,
                    "value": independent.tuple_(k, rng.randint(0, 4))})
    out += [_unkeyed(rng) for _ in range(rng.randint(0, 3))]
    if seed % 2:  # half the histories come indexed, as run_test leaves them
        out = h.index(out)
    return out


_SPLIT_CASES = {
    **{f"random-{s}": _random_keyed_history(s) for s in range(8)},
    "empty": [],
    "no-keyed-ops": [_unkeyed(random.Random(i)) for i in range(6)],
    "key-once": [
        {"type": "info", "f": "start", "process": "nemesis"},
        invoke("read", independent.tuple_("x", None), 0, index=5),
        invoke("read", independent.tuple_(3, None), 1),
        {"type": "info", "f": "stop", "value": None, "process": "nemesis"},
        ok("read", independent.tuple_(3, 2), 1),
        {"type": "info", "f": "start", "process": "nemesis"},
    ],
}


@pytest.mark.parametrize("name", sorted(_SPLIT_CASES))
def test_split_matches_per_key_subhistory(name):
    hist = _SPLIT_CASES[name]
    keys, subs, unkeyed = independent.split(hist)
    oracle = independent.history_keys(hist)
    assert keys == oracle
    assert subs == [h.index(independent.subhistory(k, hist)) for k in oracle]
    assert unkeyed == sum(not independent.is_tuple(o.get("value")) for o in hist)


def test_independent_fallback_matches_batch():
    """A checker without ``check_batch`` takes the per-key path (as a
    batch that raises does) over the same subhistories, nemesis ops
    included, with the same results as the batch path."""
    from jepsen_tpu.checker import Checker

    class Seen(Checker):
        def check(self, test, history, opts):
            vals = [o.get("value") for o in history if o["type"] == "ok"]
            return {"valid?": all(v != 2 for v in vals),
                    "history": [dict(o) for o in history]}

    class Batch(Seen):
        def check_batch(self, test, histories, opts):
            return [self.check(test, hh, opts) for hh in histories]

    hist = pairs(
        ("read", independent.tuple_("a", None), independent.tuple_("a", 1), 0),
        ("read", independent.tuple_("b", None), independent.tuple_("b", 2), 1),
    )
    hist.insert(2, {"type": "info", "f": "kill", "value": None,
                    "process": "nemesis"})
    batch = independent.checker(Batch()).check({"name": "t"}, hist, {})
    per_key = independent.checker(Seen()).check({"name": "t"}, hist, {})
    assert per_key == batch
    assert batch["failures"] == ["b"]
    for k in ("a", "b"):
        assert batch["results"][k]["history"] == h.index(
            independent.subhistory(k, hist))
        assert "kill" in [o["f"] for o in batch["results"][k]["history"]]


# ---------------------------------------------------------------------------
# bank
# ---------------------------------------------------------------------------


def bank_test():
    return {"accounts": [0, 1], "total-amount": 10}


def test_bank_valid_reads():
    h = pairs(("read", None, {0: 5, 1: 5}, 0), ("read", None, {0: 7, 1: 3}, 1))
    res = bank.checker().check(bank_test(), h, {})
    assert res["valid?"] is True


def test_bank_catches_lost_money():
    h = pairs(("read", None, {0: 5, 1: 4}, 0))
    res = bank.checker().check(bank_test(), h, {})
    assert res["valid?"] is False
    assert res["bad-read-count"] == 1


def test_bank_catches_negative_balance():
    h = pairs(("read", None, {0: 12, 1: -2}, 0))
    assert bank.checker().check(bank_test(), h, {})["valid?"] is False
    assert bank.checker(negative_balances_ok=True).check(bank_test(), h, {})["valid?"] is True


def test_bank_generator_shape():
    h = gt.quick({"concurrency": 2}, gen.clients(gen.limit(50, bank.generator())))
    fs = {o["f"] for o in h}
    assert fs == {"read", "transfer"}
    for o in h:
        if o["f"] == "transfer":
            v = o["value"]
            assert v["from"] != v["to"] and v["amount"] >= 1


# ---------------------------------------------------------------------------
# sets
# ---------------------------------------------------------------------------


def test_set_workload_unique_adds():
    w = sets.workload()
    h = gt.quick({"concurrency": 2}, gen.clients(gen.limit(20, w["generator"])))
    vals = [o["value"] for o in h]
    assert len(vals) == len(set(vals)) == 20


# ---------------------------------------------------------------------------
# long fork
# ---------------------------------------------------------------------------


def test_long_fork_detects_incomparable_reads():
    n = 2
    h = pairs(
        ("txn", [["r", 0, None], ["r", 1, None]], [["r", 0, 1], ["r", 1, None]], 0),
        ("txn", [["r", 0, None], ["r", 1, None]], [["r", 0, None], ["r", 1, 1]], 1),
    )
    res = long_fork.checker(n).check({}, h, {})
    assert res["valid?"] is False
    assert res["fork-count"] == 1


def test_long_fork_accepts_chain():
    n = 2
    h = pairs(
        ("txn", None, [["r", 0, 1], ["r", 1, None]], 0),
        ("txn", None, [["r", 0, 1], ["r", 1, 1]], 1),
        ("txn", None, [["r", 0, None], ["r", 1, None]], 2),
    )
    assert long_fork.checker(n).check({}, h, {})["valid?"] is True


# ---------------------------------------------------------------------------
# causal
# ---------------------------------------------------------------------------


def test_causal_read_your_writes_violation():
    h = pairs(
        ("write", 1, 1, 0),
        ("read", None, None, 0),  # should have seen 1
    )
    assert causal.checker().check({}, h, {})["valid?"] is False


def test_causal_valid_session():
    h = pairs(
        ("write", 1, 1, 0),
        ("read", None, 1, 0),
        ("write", 2, 2, 0),
        ("read", None, 2, 0),
    )
    assert causal.checker().check({}, h, {})["valid?"] is True


def test_causal_reverse_detects_reorder():
    h = pairs(
        ("insert", 0, 0, 0),
        ("insert", 1, 1, 0),
        ("read", None, [1], 1),  # saw 1, missed earlier-acked 0
    )
    assert causal.reverse_checker().check({}, h, {})["valid?"] is False


def test_causal_reverse_accepts_prefix():
    h = pairs(
        ("insert", 0, 0, 0),
        ("insert", 1, 1, 0),
        ("read", None, [0, 1], 1),
        ("read", None, [0], 1),
    )
    assert causal.reverse_checker().check({}, h, {})["valid?"] is True


# ---------------------------------------------------------------------------
# adya g2
# ---------------------------------------------------------------------------


def test_adya_g2_detects_write_skew():
    h = pairs(
        ("txn", {"key": 0, "id": 1}, {"key": 0, "id": 1, "read": [None, None]}, 0),
        ("txn", {"key": 0, "id": 2}, {"key": 0, "id": 2, "read": [None, None]}, 1),
    )
    assert adya.checker().check({}, h, {})["valid?"] is False


def test_adya_g2_accepts_one_commit():
    h = [
        *pairs(("txn", {"key": 0, "id": 1}, {"key": 0, "id": 1, "read": [None, None]}, 0)),
        {"type": "invoke", "f": "txn", "value": {"key": 0, "id": 2}, "process": 1,
         "time": 10, "index": 10},
        {"type": "fail", "f": "txn", "value": {"key": 0, "id": 2}, "process": 1,
         "time": 11, "index": 11},
    ]
    assert adya.checker().check({}, h, {})["valid?"] is True


# ---------------------------------------------------------------------------
# elle workloads end-to-end through the simulator
# ---------------------------------------------------------------------------


def test_append_workload_generator_and_checker():
    w = append.workload({"seed": 3})
    h = gt.quick({"concurrency": 3}, gen.clients(gen.limit(30, w["generator"])))
    assert all(o["f"] == "txn" for o in h)
    mop_fs = {m[0] for o in h for m in o["value"]}
    assert mop_fs <= {"append", "r"}
    # A serial (invoke-only) history completed ok must check valid.
    completed = []
    state = {}
    for i, o in enumerate(h):
        comp_mops = []
        for f, k, v in o["value"]:
            if f == "append":
                state.setdefault(k, []).append(v)
                comp_mops.append([f, k, v])
            else:
                comp_mops.append(["r", k, list(state.get(k, []))])
        completed.append({**o, "time": 2 * i, "index": 2 * i})
        completed.append({**o, "type": "ok", "value": comp_mops, "time": 2 * i + 1,
                          "index": 2 * i + 1})
    res = w["checker"].check({}, completed, {})
    assert res["valid?"] is True


def test_wr_workload_generator_and_checker():
    w = wr.workload({"seed": 5})
    h = gt.quick({"concurrency": 2}, gen.clients(gen.limit(20, w["generator"])))
    mop_fs = {m[0] for o in h for m in o["value"]}
    assert mop_fs <= {"w", "r"}
    state = {}
    completed = []
    for i, o in enumerate(h):
        comp_mops = []
        for f, k, v in o["value"]:
            if f == "w":
                state[k] = v
                comp_mops.append([f, k, v])
            else:
                comp_mops.append(["r", k, state.get(k)])
        completed.append({**o, "time": 2 * i, "index": 2 * i})
        completed.append({**o, "type": "ok", "value": comp_mops, "time": 2 * i + 1,
                          "index": 2 * i + 1})
    res = w["checker"].check({}, completed, {})
    assert res["valid?"] is True


# ---------------------------------------------------------------------------
# linearizable-register bundle through the full interpreter
# ---------------------------------------------------------------------------


def test_linearizable_register_workload_end_to_end():
    from jepsen_tpu import core

    w = linearizable_register.workload(
        {"concurrency": 4, "threads-per-key": 2, "key-count": 4, "per-key-limit": 6,
         "algorithm": "wgl"}
    )

    class MultiKeyAtomClient(testkit.AtomClient):
        """Routes tagged values to per-key cells."""

        def __init__(self, cells=None):
            super().__init__(testkit.AtomCell())
            self.cells = cells if cells is not None else {}

        def open(self, test, node):
            c = MultiKeyAtomClient(self.cells)
            c.stats = self.stats
            c.opened = True
            return c

        def invoke(self, test, op):
            k = independent.tuple_key(op["value"])
            v = independent.tuple_value(op["value"])
            cell = self.cells.setdefault(k, testkit.AtomCell())
            inner = {**op, "value": v}
            self.cell = cell
            comp = super().invoke(test, inner)
            return {**comp, "value": independent.tuple_(k, comp.get("value"))}

    t = testkit.noop_test(
        name="linreg",
        concurrency=4,
        client=MultiKeyAtomClient(),
        generator=gen.clients(w["generator"]),
        checker=w["checker"],
    )
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        t["store-dir"] = d
        completed = core.run_test(t)
    assert completed["results"]["valid?"] is True
    assert len(completed["results"]["results"]) == 4  # all keys checked


def test_long_fork_reads_do_not_consume_write_keys():
    # Regression: reads must peek at the write-key cursor, not advance it —
    # otherwise groups end up with never-written keys.
    g = gen.limit(40, long_fork.generator(n=3))
    h = gt.quick({"concurrency": 2}, gen.clients(g))
    written = sorted(
        m[1]
        for o in h
        if o["type"] == "invoke"
        for m in (o.get("value") or [])
        if m[0] == "w"
    )
    # Write keys are dense: 0..len-1, no gaps from read consumption.
    assert written == list(range(len(written)))


# ---------------------------------------------------------------------------
# Monotonic (cockroach/tidb/faunadb harness pattern)
# ---------------------------------------------------------------------------


def test_monotonic_valid():
    from jepsen_tpu.workloads import monotonic

    hist = h.index([
        h.op(h.INVOKE, 0, "inc", None, time=10), h.op(h.OK, 0, "inc", 1, time=20),
        h.op(h.INVOKE, 1, "read", None, time=30), h.op(h.OK, 1, "read", 1, time=40),
        h.op(h.INVOKE, 0, "inc", None, time=50), h.op(h.INFO, 0, "inc", None, time=60),
        h.op(h.INVOKE, 1, "read", None, time=70), h.op(h.OK, 1, "read", 2, time=80),
    ])
    res = monotonic.checker().check({}, hist, {})
    assert res["valid?"] is True
    assert res["reads"] == 2 and res["incs"] == 2


def test_monotonic_regression():
    from jepsen_tpu.workloads import monotonic

    hist = h.index([
        h.op(h.INVOKE, 0, "inc", None, time=10), h.op(h.OK, 0, "inc", 1, time=20),
        h.op(h.INVOKE, 0, "inc", None, time=25), h.op(h.OK, 0, "inc", 2, time=28),
        h.op(h.INVOKE, 1, "read", None, time=30), h.op(h.OK, 1, "read", 2, time=40),
        # completes after the read above BEGAN? no: begins at 50 > 40, sees 1: regression
        h.op(h.INVOKE, 2, "read", None, time=50), h.op(h.OK, 2, "read", 1, time=60),
    ])
    res = monotonic.checker().check({}, hist, {})
    assert res["valid?"] is False
    assert res["errors"][0]["type"] == "nonmonotonic"
    assert res["errors"][0]["went"] == [2, 1]


def test_monotonic_impossible():
    from jepsen_tpu.workloads import monotonic

    hist = h.index([
        h.op(h.INVOKE, 0, "inc", None, time=10), h.op(h.OK, 0, "inc", 1, time=20),
        h.op(h.INVOKE, 1, "read", None, time=30), h.op(h.OK, 1, "read", 7, time=40),
    ])
    res = monotonic.checker().check({}, hist, {})
    assert res["valid?"] is False
    assert res["errors"][0]["type"] == "impossible"


def test_monotonic_concurrent_reads_ok():
    from jepsen_tpu.workloads import monotonic

    # Overlapping reads may disagree in either direction.
    hist = h.index([
        h.op(h.INVOKE, 0, "inc", None, time=5), h.op(h.OK, 0, "inc", 1, time=6),
        h.op(h.INVOKE, 1, "read", None, time=10), 
        h.op(h.INVOKE, 2, "read", None, time=12),
        h.op(h.OK, 1, "read", 1, time=30),
        h.op(h.OK, 2, "read", 0, time=32),
    ])
    res = monotonic.checker().check({}, hist, {})
    assert res["valid?"] is True


def test_monotonic_concurrent_inc_read_valid():
    """An inc still in flight may already have taken effect: a read
    observing it is legal (regression for the invocation-bound rule)."""
    from jepsen_tpu.workloads import monotonic

    hist = h.index([
        h.op(h.INVOKE, 0, "inc", None, time=10),
        h.op(h.INVOKE, 1, "read", None, time=15),
        h.op(h.OK, 1, "read", 1, time=20),
        h.op(h.OK, 0, "inc", 1, time=30),
    ])
    res = monotonic.checker().check({}, hist, {})
    assert res["valid?"] is True


# ---------------------------------------------------------------------------
# Sequential (cockroach/tidb/dgraph harness pattern)
# ---------------------------------------------------------------------------


def test_sequential_valid_prefixes():
    from jepsen_tpu.workloads import sequential

    hist = h.index([
        h.op(h.INVOKE, 0, "read", [1, None], time=10),
        h.op(h.OK, 0, "read", [1, []], time=20),
        h.op(h.INVOKE, 0, "read", [1, None], time=30),
        h.op(h.OK, 0, "read", [1, [0, 1, 2]], time=40),
    ])
    res = sequential.checker().check({}, hist, {})
    assert res["valid?"] is True and res["reads"] == 2


def test_sequential_hole_detected():
    from jepsen_tpu.workloads import sequential

    hist = h.index([
        h.op(h.INVOKE, 0, "read", [3, None], time=10),
        h.op(h.OK, 0, "read", [3, [0, 2]], time=20),  # key 1 missing below max 2
    ])
    res = sequential.checker().check({}, hist, {})
    assert res["valid?"] is False
    assert res["errors"][0]["missing"] == [1]
    assert res["errors"][0]["chain"] == 3


def test_sequential_workload_runs_in_interpreter():
    from jepsen_tpu.workloads import sequential

    wl = sequential.workload({"chain-count": 3, "keys-per-chain": 3, "concurrency": 4, "seed": 9})
    chains: dict = {}

    class ChainClient(testkit.AtomClient):
        def invoke(self, test, op):
            f = op["f"]
            if f == "write":
                c, i = op["value"]
                with self.cell.lock:
                    chains.setdefault(c, []).append(i)
                return {**op, "type": "ok"}
            c, _ = op["value"]
            with self.cell.lock:
                seen = sorted(chains.get(c, []))
            return {**op, "type": "ok", "value": [c, seen]}

    t = testkit.noop_test(
        name="seq",
        concurrency=4,
        client=ChainClient(testkit.AtomCell()),
        generator=gen.clients(gen.time_limit(3, wl["generator"])),
        checker=wl["checker"],
    )
    import tempfile

    from jepsen_tpu import core

    with tempfile.TemporaryDirectory() as d:
        completed = core.run_test({**t, "store-dir": d})
    assert completed["results"]["valid?"] is True
    assert completed["results"]["reads"] > 0
    # every chain was written in order (thread-ownership serializes them)
    for c, seq in chains.items():
        assert seq == sorted(seq), (c, seq)
