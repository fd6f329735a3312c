"""Seeded register histories: the benchmark's own copy of the generator.

``register_history`` and ``corrupt`` reproduce ``tools/genhist.py``'s
``valid_register_history`` and ``corrupt`` draw for draw (the copy check
in ``benchmark/tests/test_gen.py`` holds them equal), so that a change
to the program cannot move the inputs.  The copy builds the op dicts
itself and imports nothing of the program.

One addition: ``register_history`` can record which ``:info`` write or
cas took effect.  The simulated register applies every completed op at
its completion event, and an ``:info`` op's effect lands there or never;
that record is the witness ``benchmark/reference.py`` replays.
"""

from __future__ import annotations

import random

INVOKE, OK, FAIL, INFO = "invoke", "ok", "fail", "info"

#: how the program tags a value with its key (``independent.tuple_``)
KEY_SENTINEL = "__independent-key__"


def _op(type_: str, process: int, f: str, value, t: int) -> dict:
    return {"type": type_, "process": process, "f": f, "value": value,
            "time": t}


def index(history: list[dict]) -> list[dict]:
    """Each op with its position as ``index``."""
    return [o if o.get("index") == i else {**o, "index": i}
            for i, o in enumerate(history)]


def register_history(n_ops: int, n_procs: int, seed: int,
                     info_rate: float, n_values: int,
                     fs=("read", "write", "cas"),
                     landed: set | None = None) -> list[dict]:
    """A linearizable CAS-register history of ``n_ops`` invocations by
    ``n_procs`` processes.  ``landed``, when given, receives the
    positions of the ``:info`` completions whose effect took place."""
    rng = random.Random(seed)
    hist: list[dict] = []
    state = None
    live: dict[int, dict] = {}
    invoked = 0
    t = 0
    while invoked < n_ops or live:
        t += 1
        can_invoke = [p for p in range(n_procs) if p not in live]
        if can_invoke and invoked < n_ops and (not live or rng.random() < 0.6):
            p = rng.choice(can_invoke)
            f = rng.choice(fs)
            if f == "read":
                v = None
            elif f == "write":
                v = rng.randrange(n_values)
            else:
                old = (state if state is not None and rng.random() < 0.7
                       else rng.randrange(n_values))
                v = [old, rng.randrange(n_values)]
            live[p] = _op(INVOKE, p, f, v, t)
            hist.append(live[p])
            invoked += 1
        else:
            p = rng.choice(list(live))
            inv = live.pop(p)
            f, v = inv["f"], inv["value"]
            if rng.random() < info_rate:
                o = _op(INFO, p, f, v, t)
                if rng.random() < 0.5:  # the effect may have landed anyway
                    if f == "write":
                        state = v
                    elif f == "cas" and state == v[0]:
                        state = v[1]
                    if landed is not None and f != "read":
                        landed.add(len(hist))
            elif f == "read":
                o = _op(OK, p, "read", state, t)
            elif f == "write":
                state = v
                o = _op(OK, p, "write", v, t)
            else:
                ok = state == v[0]
                if ok:
                    state = v[1]
                o = _op(OK if ok else FAIL, p, "cas", v, t)
            hist.append(o)
    return index(hist)


def corrupt(history: list[dict], seed: int, n_flips: int = 1) -> list[dict]:
    """Shift ``n_flips`` ok reads to a value no op ever writes."""
    rng = random.Random(seed)
    hist = [dict(o) for o in history]
    reads = [i for i, o in enumerate(hist)
             if o["type"] == OK and o["f"] == "read" and o["value"] is not None]
    for i in rng.sample(reads, min(n_flips, len(reads))):
        hist[i]["value"] = hist[i]["value"] + 1000
    return index(hist)


def key_seed(seed: int, draw: int, key: int) -> int:
    """The seed of one key's history: distinct for every (seed, draw,
    key), whatever the size of ``seed``."""
    return (seed * 1_000_003 + draw) * 65_536 + key


def key_histories(seed: int, draw: int, keys: int, shape: dict
                  ) -> tuple[list[list[dict]], list[set]]:
    """``keys`` per-key histories of one shape (``ops``, ``procs``,
    ``info_rate``, ``values``), every ``corrupt_every``-th corrupted.
    Returns the histories and each one's ``landed`` witness."""
    subs, witnesses = [], []
    every = shape.get("corrupt_every") or 0
    for k in range(keys):
        s = key_seed(seed, draw, k)
        landed: set = set()
        hist = register_history(shape["ops"], shape["procs"], s,
                                shape["info_rate"], shape["values"],
                                landed=landed)
        if every and k % every == every - 1:
            hist = corrupt(hist, s)
        subs.append(hist)
        witnesses.append(landed)
    return subs, witnesses


def keyed_history(subs: list[list[dict]], procs: int,
                  labels: list[int] | None = None) -> list[dict]:
    """One independent-keyed history from per-key histories: values
    tagged with their key, each key with processes of its own, all ops
    interleaved by time (ties by key, then position).  ``labels[i]`` is
    the key of ``subs[i]`` (default: i)."""
    tagged = []
    for i, hist in enumerate(subs):
        k = labels[i] if labels is not None else i
        for o in hist:
            tagged.append(((o["time"], k, o["index"]), {
                **o, "process": o["process"] + procs * k,
                "value": [KEY_SENTINEL, k, o["value"]],
            }))
    tagged.sort(key=lambda t: t[0])
    return index([o for _, o in tagged])
