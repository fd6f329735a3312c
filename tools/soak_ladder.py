"""Soak the full batch ladder against the exact oracle.

Random small histories in adversarial shapes (info-heavy, crash groups,
cas, corruptions), checked in batches through the COMPLETE ladder
(greedy rung, carried frontiers, saturating prune, worker confirmation
on and off, every DEDUP BACKEND — the ``dedup_backend`` axis
randomizes sort vs bucket vs pallas per batch) and compared
verdict-by-verdict against ``wgl_cpu.sweep_analysis``.  Any non-unknown
disagreement is a soundness bug — print it and exit 1.

  python tools/soak_ladder.py [--minutes N] [--seed S] [--batches N]
                              [--dedup-backend sort|bucket|pallas|both|all]

``--batches`` runs a fixed batch count instead of a time budget (the
differential-soak acceptance gate pins a count, not a duration);
``--dedup-backend`` pins the dedup axis (default: all, randomized;
"both" keeps the PR-2 sort/bucket pair).  When the pallas axis is
live, the wide-rung routing floor is lowered to the soak's capacities
(JEPSEN_TPU_PALLAS_MIN_CAPACITY=64, unless already set) so the fused
kernel actually executes — in interpret mode on CPU — instead of
routing every narrow rung back to bucket.
"""

from __future__ import annotations

import random
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "tools"))
sys.path.insert(0, str(ROOT))

from genhist import corrupt, valid_register_history  # noqa: E402

from jepsen_tpu import history as h  # noqa: E402
from jepsen_tpu import models as m  # noqa: E402
from jepsen_tpu.checker import wgl_cpu  # noqa: E402
from jepsen_tpu.parallel import batch_analysis  # noqa: E402


def random_history(rng, n_procs, n_ops, values, info_w):
    hist = []
    live = {}
    placed = 0
    while placed < n_ops:
        p = rng.randrange(n_procs)
        if p in live:
            inv = live.pop(p)
            outcome = rng.choices(
                [h.OK, h.FAIL, h.INFO], weights=[6, 1, info_w]
            )[0]
            v = inv["value"]
            if inv["f"] == "read":
                v = rng.randrange(values) if outcome == h.OK else None
            hist.append(h.op(outcome, p, inv["f"], v))
        else:
            f = rng.choice(["read", "write", "write", "cas"])
            v = (
                None if f == "read"
                else rng.randrange(values) if f == "write"
                else [rng.randrange(values), rng.randrange(values)]
            )
            inv = h.op(h.INVOKE, p, f, v)
            live[p] = inv
            hist.append(inv)
            placed += 1
    return h.index(hist)


def main() -> int:
    minutes = 20.0
    seed = 45100
    max_batches = None
    dedup_axis = "all"
    if "--minutes" in sys.argv:
        minutes = float(sys.argv[sys.argv.index("--minutes") + 1])
    if "--seed" in sys.argv:
        seed = int(sys.argv[sys.argv.index("--seed") + 1])
    if "--batches" in sys.argv:
        max_batches = int(sys.argv[sys.argv.index("--batches") + 1])
    if "--dedup-backend" in sys.argv:
        dedup_axis = sys.argv[sys.argv.index("--dedup-backend") + 1]
        assert dedup_axis in ("sort", "bucket", "pallas", "both", "all"), \
            dedup_axis
    if dedup_axis in ("pallas", "all"):
        # make the fused kernel actually run at the soak's capacities
        # (interpret mode on CPU) instead of routing back to bucket
        import os

        os.environ.setdefault("JEPSEN_TPU_PALLAS_MIN_CAPACITY", "64")
    axis_pool = {
        "both": ["sort", "bucket"],
        "all": ["sort", "bucket", "pallas"],
    }.get(dedup_axis, [dedup_axis])
    rng = random.Random(seed)
    model = m.CASRegister(None)
    deadline = time.monotonic() + minutes * 60
    batches = checked = disagreements = 0
    while (time.monotonic() < deadline if max_batches is None
           else batches < max_batches):
        hists = []
        for _ in range(16):
            kind = rng.random()
            if kind < 0.5:
                hist = random_history(
                    rng, rng.randrange(2, 6), rng.randrange(6, 18),
                    rng.randrange(2, 5), rng.choice([1, 3, 6]),
                )
            else:
                hist = valid_register_history(
                    rng.randrange(20, 60), rng.randrange(2, 6),
                    seed=rng.randrange(1 << 30),
                    info_rate=rng.choice([0.0, 0.1, 0.3, 0.5]),
                )
                if rng.random() < 0.5:
                    hist = corrupt(hist, seed=rng.randrange(1 << 30))
            hists.append(hist)
        confirm = rng.choice([True, False])
        dedup = rng.choice(axis_pool)
        results = batch_analysis(
            model, hists, capacity=(rng.choice([16, 32, 64]), 256),
            cpu_fallback=False, exact_escalation=(),
            confirm_refutations=confirm,
            greedy_first=rng.random() < 0.8,
            dedup_backend=dedup,
        )
        batches += 1
        for i, (hist, r) in enumerate(zip(hists, results)):
            if r["valid?"] == "unknown":
                continue
            truth = wgl_cpu.sweep_analysis(model, hist, max_configs=500_000)
            checked += 1
            if truth["valid?"] != "unknown" and truth["valid?"] != r["valid?"]:
                disagreements += 1
                print("DISAGREEMENT", {"batch": batches, "i": i,
                                       "got": r, "want": truth["valid?"],
                                       "confirm": confirm, "dedup": dedup,
                                       "hist": hist}, flush=True)
        if batches % 20 == 0:
            print(f"soak: {batches} batches, {checked} verdicts checked, "
                  f"{disagreements} disagreements", flush=True)
    print(f"DONE: {batches} batches, {checked} verdicts, "
          f"{disagreements} disagreements", flush=True)
    return 1 if disagreements else 0


if __name__ == "__main__":
    sys.exit(main())
