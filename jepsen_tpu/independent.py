"""Independent: scale a single-key workload across a whole keyspace.

Mirrors ``jepsen.independent`` (reference:
jepsen/src/jepsen/independent.clj).  Linearizability checking is NP-hard in
history length, so instead of one long history the workload is sharded into
many independent keys with bounded per-key op counts
(independent.clj:2-7) — and the checker splits the history back out per key
(independent.clj:240-317).  This keyspace axis is exactly what the TPU
backend turns into the vmapped batch dimension (SURVEY.md §2.5 item 4;
jepsen_tpu.parallel.batch_analysis).

Values are tagged as ``(key, value)`` tuples (the reference uses a
MapEntry, independent.clj:21-29); ``tuple_/is_tuple/ktuple`` handle the
tagging.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Mapping, Sequence

from jepsen_tpu import faults, obs
from jepsen_tpu import generator as gen
from jepsen_tpu import store
from jepsen_tpu.checker import Checker, merge_valid, check_safe
from jepsen_tpu.utils import bounded_pmap

KEY_SENTINEL = "__independent-key__"


def tuple_(key, value) -> list:
    """Tag a value with its key (independent.clj:21-25).  JSON-friendly
    2-lists, round-tripping through history.jsonl."""
    return [KEY_SENTINEL, key, value]


def is_tuple(v) -> bool:
    return isinstance(v, (list, tuple)) and len(v) == 3 and v[0] == KEY_SENTINEL


def tuple_key(v):
    return v[1]


def tuple_value(v):
    return v[2]


# ---------------------------------------------------------------------------
# Generators
# ---------------------------------------------------------------------------


def _key_wrap(key, sub: gen.Gen) -> gen.Gen:
    """Ops from sub get values tagged with key."""
    return gen.map_gen(lambda o: {**o, "value": tuple_(key, o.get("value"))}, sub)


def sequential_generator(keys: Sequence, gen_fn: Callable[[Any], Any]) -> gen.Gen:
    """One key at a time: run gen_fn(k) to exhaustion for each k in order
    (independent.clj:31-66)."""
    return gen._Seq(tuple(_key_wrap(k, gen.to_gen(gen_fn(k))) for k in keys))


@dataclasses.dataclass(frozen=True)
class ConcurrentGenerator(gen.Gen):
    """Partition client threads into groups of n; each group works one key's
    generator, pulling a fresh key whenever its generator is exhausted
    (independent.clj:103-238).

    ``keys`` is consumed lazily; when it runs dry and every group's
    generator is exhausted, the whole generator is done.
    """

    n: int  # threads per group
    gen_fn: Callable
    keys: tuple
    groups: Mapping  # group_id -> (key, Gen) | None (None = retired)

    def _group_of(self, thread) -> int | None:
        if thread == gen.NEMESIS:
            return None
        return thread // self.n

    def _group_threads(self, ctx, gid):
        return frozenset(
            t for t in ctx.all_threads() if t != gen.NEMESIS and t // self.n == gid
        )

    def op(self, test, ctx):
        gids = sorted({g for g in (self._group_of(t) for t in ctx.all_threads()) if g is not None})
        candidates = []
        keys = self.keys
        groups = dict(self.groups)
        for gid in gids:
            state = groups.get(gid, "unset")
            if state is None:
                continue
            if state == "unset":
                if not keys:
                    groups[gid] = None
                    continue
                state = (keys[0], _key_wrap(keys[0], gen.to_gen(self.gen_fn(keys[0]))))
                keys = keys[1:]
                groups[gid] = state
            k, g = state
            sub = ctx.restrict(lambda t, gid=gid: self._group_of(t) == gid)
            r = g.op(test, sub)
            if r is None:
                # Exhausted: draw the next key for this group, if any.
                if keys:
                    nk = keys[0]
                    keys = keys[1:]
                    groups[gid] = (nk, _key_wrap(nk, gen.to_gen(self.gen_fn(nk))))
                    r = groups[gid][1].op(test, sub)
                    if r is None:
                        groups[gid] = None
                        continue
                else:
                    groups[gid] = None
                    continue
            o, g2 = r
            candidates.append({"op": o, "gen": g2, "gid": gid, "key": groups[gid][0]})
        live = ConcurrentGenerator(self.n, self.gen_fn, keys, groups)
        if not candidates:
            if any(v is not None for v in groups.values()) or keys:
                return (gen.PENDING, live)
            return None
        best = gen.soonest_op_map(candidates)
        groups[best["gid"]] = (best["key"], best["gen"])
        return (best["op"], ConcurrentGenerator(self.n, self.gen_fn, keys, groups))

    def update(self, test, ctx, event):
        thread = ctx.thread_of(event.get("process"))
        gid = self._group_of(thread) if thread is not None else None
        if gid is None:
            return self
        state = self.groups.get(gid)
        if not state:
            return self
        k, g = state
        sub = ctx.restrict(lambda t, gid=gid: self._group_of(t) == gid)
        groups = dict(self.groups)
        groups[gid] = (k, g.update(test, sub, event))
        return ConcurrentGenerator(self.n, self.gen_fn, self.keys, groups)


def concurrent_generator(n: int, keys: Sequence, gen_fn: Callable) -> gen.Gen:
    """(independent.clj:103-238).  n = threads per key-group; the test's
    concurrency should be a multiple of n."""
    return ConcurrentGenerator(n, gen_fn, tuple(keys), {})


# ---------------------------------------------------------------------------
# History surgery (independent.clj:240-264)
# ---------------------------------------------------------------------------


def history_keys(history: Sequence[Mapping]) -> list:
    """Distinct keys, in order of first appearance."""
    seen = {}
    for o in history:
        v = o.get("value")
        if is_tuple(v):
            seen.setdefault(tuple_key(v), True)
    return list(seen)


def subhistory(key, history: Sequence[Mapping]) -> list[dict]:
    """Ops for one key, values untagged; non-tuple ops (e.g. nemesis) are
    kept with their value intact (independent.clj:251-264)."""
    out = []
    for o in history:
        v = o.get("value")
        if is_tuple(v):
            if tuple_key(v) == key:
                out.append({**o, "value": tuple_value(v)})
        else:
            out.append(o)
    return out


def split(history: Sequence[Mapping]) -> tuple[list, list[list[dict]], int]:
    """Every key's subhistory in one pass over the history.

    Returns ``(keys, subs, unkeyed)``.  ``keys`` is
    ``history_keys(history)``; ``subs[i]`` equals ``subhistory(keys[i],
    history)`` re-indexed from 0, as ``jepsen_tpu.history.index`` does;
    ``unkeyed`` counts the ops with no key tag, which every subhistory
    holds.  Those ops wait in one shared list, and each key's cursor into
    it catches its subhistory up before the key's next op and at the end:
    O(N + K·S) work for N ops, K keys and S unkeyed ops, where a
    ``subhistory`` call per key costs O(K·N)."""
    shared: list = []  # unkeyed ops, in history order
    buckets: dict = {}  # key -> [subhistory, cursor into shared]

    def catch_up(b):
        sub = b[0]
        for o in shared[b[1]:]:
            sub.append({**o, "index": len(sub)})
        b[1] = len(shared)

    for o in history:
        v = o.get("value")
        if is_tuple(v):
            k = tuple_key(v)
            b = buckets.get(k)
            if b is None:
                b = buckets[k] = [[], 0]
            if b[1] < len(shared):
                catch_up(b)
            sub = b[0]
            sub.append({**o, "value": tuple_value(v), "index": len(sub)})
        else:
            shared.append(o)
    for b in buckets.values():
        catch_up(b)
    return list(buckets), [b[0] for b in buckets.values()], len(shared)


# ---------------------------------------------------------------------------
# Checker (independent.clj:266-317)
# ---------------------------------------------------------------------------


class IndependentChecker(Checker):
    """Split the history per key, run the wrapped checker on each, merge
    validity; per-key results land in ``independent/<key>/``.

    Both paths start from one ``split`` of the history (span
    ``independent.split``).  The batch path is then three more spans,
    each once per check: the wrapped checker's ladder call and evidence
    (``ladder.run``, ``checker.evidence``), and the per-key saves
    (``independent.save``)."""

    def __init__(self, checker: Checker):
        self.checker = checker

    def check(self, test, history, opts):
        opts = dict(opts or {})

        def save_key(k, sub, res, from_batch=False):
            try:
                d = store.test_dir(test) / "independent" / str(k)
                d.mkdir(parents=True, exist_ok=True)
                store._write_json(d / "results.json", res)
                store.write_history(d, sub)
            except (KeyError, OSError, TypeError):
                return  # no store configured (bare unit tests)
            # Checkers with extra artifact output (e.g. elle's anomaly
            # explanation dir) render per key too.  Only the batch path
            # needs the hook: it skips the per-key check(), which writes
            # its own artifacts on the fallback path.
            if from_batch:
                write = getattr(self.checker, "write_artifacts", None)
                if write is not None:
                    write(test, res, {**opts, "subdirectory": f"independent/{k}"})

        with obs.span("independent.split", events=len(history)) as sp:
            keys, subs, unkeyed = split(history)
            sp.set(keys=len(keys), unkeyed=unkeyed)
        batch = None
        if hasattr(self.checker, "check_batch"):
            # Batch-capable checkers (TPU elle / linearizable) take every
            # per-key subhistory in ONE call and bucket them into vmapped
            # kernel launches — the reference's bounded-pmap scale-out
            # (independent.clj:285-307) as a device batch axis.  A batch
            # failure falls back to the per-key path below so one key's
            # exception can't mask another key's real violation.
            try:
                batch = self.checker.check_batch(test, subs, opts)
            except faults.CompileError:
                raise  # every key would hit the same refusal
            except Exception as e:  # noqa: BLE001 — per-key path isolates it
                obs.counter("fault.batch.fallback", error=faults.describe(e))
                batch = None
        if batch is not None:
            with obs.span("independent.save", keys=len(keys),
                          stored=store.has_test_dir(test)):
                results = {}
                for k, sub, res in zip(keys, subs, batch):
                    results[k] = res
                    save_key(k, sub, res, from_batch=True)
        else:

            def check_key(k_sub):
                k, sub = k_sub
                sub_opts = {**opts, "subdirectory": f"independent/{k}"}
                res = check_safe(self.checker, test, sub, sub_opts)
                save_key(k, sub, res)
                return k, res

            results = dict(bounded_pmap(check_key, list(zip(keys, subs))))
        valid = merge_valid([r.get("valid?") for r in results.values()] or [True])
        failures = [k for k, r in results.items() if r.get("valid?") is not True]
        return {
            "valid?": valid,
            "results": results,
            "failures": failures,
        }


def checker(inner: Checker) -> Checker:
    return IndependentChecker(inner)
