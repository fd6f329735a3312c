"""Closed loop, one client: whole keyed histories through ``check_safe``.

Each check is one Jepsen test's history, as ``jepsen analyze`` hands it
to ``independent.checker(linearizable(cas-register))``.  Set-up draws a
pool of the traffic's ``pool`` keyed histories from ``--seed``, all of
the configuration's shape, and checks each once: that compiles, or
loads, every shape the window uses, whichever batches the ladder forms
on them.  The window then checks the pool's histories in turn until
``--seconds`` have passed; the check in flight finishes, so only whole
checks count.  Every check files its histories under a fresh run of
consecutive keys drawn from the seed (their order, and so the ladder's
batches, stay as in set-up), so no two checks hand the program the same
keyed history.  ``ops_verified_per_s`` is every op of the window's
checks over the time they took.  With ``--trace 1`` the profiler covers
the window's first check.
"""

from __future__ import annotations

import random
import time

from benchmark.gen import histories as gh


def _shape(config: dict) -> dict:
    return {"ops": config["ops_per_key"], "procs": config["procs_per_key"],
            "info_rate": config["info_rate"], "values": config["values"],
            "corrupt_every": config["corrupt_every"]}


def run(cell, devs) -> dict:
    from jepsen_tpu import checker, independent
    from jepsen_tpu.checker.linearizable import linearizable
    from jepsen_tpu.parallel import make_mesh

    cfg, traffic = cell.config, cell.traffic
    keys, shape = cfg["keys"], _shape(cfg)
    pool = [gh.key_histories(cell.seed, draw, keys, shape)
            for draw in range(traffic["pool"])]
    rng = random.Random(cell.seed)

    def labelled(subs):
        base = rng.randrange(1000) * keys
        labels = [base + i for i in range(keys)]
        return gh.keyed_history(subs, shape["procs"], labels), labels

    opts = dict(cfg["linearizable_opts"])
    if cfg["mesh_devices"] > 1:
        opts["kernel-opts"] = {**opts.get("kernel-opts", {}),
                               "mesh": make_mesh(cfg["mesh_devices"])}
    chk = independent.checker(linearizable(opts))

    def check(history):
        t0 = time.monotonic()
        res = checker.check_safe(chk, {}, history)
        return res, time.monotonic() - t0

    warm = [round(check(labelled(subs)[0])[1], 3) for subs, _ in pool]
    verdicts, truth, walls, ops = [], [], [], 0
    t_open = cell.open_window()
    while not walls or time.monotonic() - t_open < cell.seconds:
        subs, landed = pool[len(walls) % len(pool)]
        history, labels = labelled(subs)
        if not walls:
            cell.start_slice()
        res, dt = check(history)
        if len(walls) == 0:
            cell.stop_slice()
        walls.append(dt)
        ops += len(history) // 2  # one op is an invocation and its completion
        per_key = res.get("results", {})
        verdicts.extend(per_key[k].get("valid?") if k in per_key else None
                        for k in labels)
        truth.extend(zip(subs, landed))
    cell.close_window()
    return {
        "setup_end": t_open,
        "e2e": {"ops_verified_per_s": ops / sum(walls)},
        "checked_s": sum(walls),
        "verdicts": verdicts,
        "truth": truth,
        "notes": {"set-up checks (s)": warm,
                  "window checks (s)": [round(w, 3) for w in walls],
                  "ops per check": ops // len(walls)},
    }
