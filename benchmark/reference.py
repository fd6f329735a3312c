"""The plain reference: a CAS-register history's verdict from a certificate.

It imports nothing of the program.  A history from
``benchmark/gen/histories.py`` is decided by one of two certificates,
each checked against the history itself:

* invalid: an ok read returns a value that is neither the register's
  initial nil nor a value that any write or cas in the history writes.
  No order of the ops can produce it.
* valid: the generator's witness, replayed.  Every completed op takes
  effect at its completion event, which lies inside its window; an
  ``:info`` write or cas takes effect there if the witness says it
  landed, and never otherwise (a crashed op may or may not happen).
  The replay checks each ok read's value, each cas's outcome and each
  failed cas's mismatch against a sequential register.

A history that neither certificate decides returns None: it is not
compared, and the run reports how many there were.
"""

from __future__ import annotations


def _written(history) -> set:
    out = set()
    for o in history:
        if o["type"] != "invoke":
            continue
        if o["f"] == "write":
            out.add(o["value"])
        elif o["f"] == "cas":
            out.add(o["value"][1])
    return out


def verdict(history: list[dict], landed: set) -> bool | None:
    """True or False by certificate, None where neither applies.
    ``landed`` holds the positions of the ``:info`` completions whose
    effect took place."""
    written = _written(history)
    for o in history:
        if (o["type"] == "ok" and o["f"] == "read" and o["value"] is not None
                and o["value"] not in written):
            return False
    state = None
    pending: dict = {}
    for pos, o in enumerate(history):
        if o["type"] == "invoke":
            pending[o["process"]] = o
            continue
        inv = pending.pop(o["process"], None)
        if inv is None or inv["f"] != o["f"]:
            return None
        f, v = inv["f"], inv["value"]
        if o["type"] == "info":
            if pos in landed:
                if f == "write":
                    state = v
                elif f == "cas" and state == v[0]:
                    state = v[1]
        elif o["type"] == "fail":
            if f != "cas" or state == v[0]:
                return None
        elif f == "read":
            if o["value"] != state:
                return None
        elif f == "write":
            state = v
        elif state == v[0]:
            state = v[1]
        else:
            return None
    return True


def compare(verdicts: list, truths: list) -> dict:
    """Counts of one run's answers against the reference's.  A verdict
    of None is an answer that never came; any other verdict than True
    or False (``"unknown"``, a refusal) is a failure to decide, which
    is no wrong answer."""
    out = {"wrong": 0, "missing": 0, "unknown": 0, "undecided": 0}
    for got, want in zip(verdicts, truths, strict=True):
        if got is None:
            out["missing"] += 1
        elif got not in (True, False):
            out["unknown"] += 1
        elif want is None:
            out["undecided"] += 1
        elif got is not want:
            out["wrong"] += 1
    return out
