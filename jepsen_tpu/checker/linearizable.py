"""The linearizable checker front-end (reference: checker.clj:185-216).

Chooses an analysis backend by ``algorithm`` the way the reference chooses
between knossos's ``:linear``/``:wgl``/``competition`` engines:

  * ``"wgl"``          — the CPU DFS oracle (jepsen_tpu.checker.wgl_cpu);
  * ``"sweep"``        — the CPU configuration-set sweep (the TPU kernel's
    semantics oracle);
  * ``"tpu"``          — the chunked exact device engine (jepsen_tpu.ops.
    wgl.analysis: carried-frontier chunk scans, content-decided kills);
  * ``"competition"``  — the measured-fastest ladder, mirroring
    knossos.competition's race semantics with a deterministic order
    instead of racing threads: (0) the DEVICE greedy witness walk
    (wgl.greedy_analysis) — one config, no frontier buffers; most valid
    histories (including the 10k-op register that exhausts every
    fixed-capacity beam) resolve here in one scan; (1) the async beam
    kernel at an escalating capacity ladder — a surviving frontier is a
    constructive witness (True), a lossless death is confirmed against
    the exact CPU sweep bounded to the failure prefix; (2) on
    "unknown", the greedy CPU DFS; (3) still unknown → the chunked
    exact device engine, whose refutations are final and whose stats
    quantify the verified prefix.

On failure, ``final-paths`` / ``configs`` are truncated to 10 entries, as
the reference does because writing them out "can take *hours*"
(checker.clj:213-216).
"""

from __future__ import annotations

from typing import Mapping

from jepsen_tpu import models as m
from jepsen_tpu import obs, store
from jepsen_tpu.checker import Checker, UNKNOWN
from jepsen_tpu.checker import wgl_cpu
from jepsen_tpu.obs import provenance as _prov


def _resolve_model(model) -> m.Model:
    if isinstance(model, str):
        return m.model(model)
    return model


class Linearizable(Checker):
    def __init__(self, opts: Mapping):
        if "model" not in opts or opts["model"] is None:
            raise ValueError(
                f"the linearizable checker requires a model, got {opts.get('model')!r}"
            )
        self.model = _resolve_model(opts["model"])
        self.algorithm = opts.get("algorithm", "competition")
        self.kernel_opts = dict(opts.get("kernel-opts", {}))

    def _analyze(self, history, deadline=None):
        if self.algorithm == "wgl":
            return _prov.attach(
                wgl_cpu.dfs_analysis(self.model, history),
                [{"event": "engine.dfs"}], engine={"engine": "wgl-dfs"})
        if self.algorithm == "sweep":
            return _prov.attach(
                wgl_cpu.sweep_analysis(self.model, history),
                [{"event": "engine.sweep"}], engine={"engine": "wgl-sweep"})
        from jepsen_tpu.ops import wgl as wgl_tpu

        if self.algorithm == "tpu":
            return wgl_tpu.analysis(self.model, history, deadline=deadline,
                                    **self.kernel_opts)
        if self.algorithm == "competition":
            return self._competition(history, wgl_tpu, deadline)
        raise ValueError(f"unknown linearizability algorithm {self.algorithm!r}")

    def _competition(self, history, wgl_tpu, deadline=None):
        """Fast engines first, exact ones on demand (see module doc).

        Tunables ride ``kernel-opts``: ``async-capacity`` sizes the beam
        ladder (the chunked engine's own ``capacity`` escalation ladder
        is a separate knob, forwarded untouched), ``confirm-max-configs``
        bounds the refutation-confirmation sweep (same default as
        parallel.batch_analysis's confirm_max_configs)."""
        path: list[dict] = []  # the decision-path trail (obs.provenance)

        def _fin(res, engine_name):
            """Attach the engine-fallback trail before a result leaves
            the competition — the evidence bundle's decision path."""
            return _prov.attach(res, path, engine={"engine": engine_name})

        if deadline is not None and deadline.expired():
            # the budget was spent before this key's check began (e.g. by
            # earlier keys of an independent checker): degrade attributably
            path.append({"event": "fault.deadline", "at": "pre-check"})
            return _fin({
                "valid?": UNKNOWN,
                "cause": "deadline-exceeded: check budget exhausted",
            }, "competition")
        ladder = self.kernel_opts.get("async-capacity", (256, 1024))
        if isinstance(ladder, int):
            ladder = (ladder,)
        confirm_cap = self.kernel_opts.get("confirm-max-configs", 2_000_000)
        # Rung 0: the greedy witness walk — one config, no frontier
        # buffers, resolves most valid histories (incl. the 10k-op
        # register that exhausts every fixed-capacity beam) in one scan.
        # ``greedy-first: False`` in kernel-opts disables it (mirror of
        # batch_analysis's greedy_first knob).
        if self.kernel_opts.get("greedy-first", True):
            g = wgl_tpu.greedy_analysis(self.model, history)
            if g["valid?"] is True:
                path.append({"event": "engine.greedy", "outcome": "valid"})
                return _fin(g, "greedy")
            if "not tensorizable" in str(g.get("cause", "")):
                path.append({"event": "engine.greedy",
                             "outcome": "not-tensorizable"})
                path.append({"event": "cpu-fallback", "engine": "dfs"})
                return _fin(wgl_cpu.analysis(self.model, history), "wgl-dfs")
            path.append({"event": "engine.greedy", "outcome": "stuck"})
        for cap in ladder:
            a = wgl_tpu.analysis_async(self.model, history, capacity=int(cap))
            path.append({"event": "async.capacity", "capacity": int(cap),
                         "outcome": _prov.verdict_str(a["valid?"])})
            if a["valid?"] is True:
                return _fin(a, "async")
            if a["valid?"] is False:
                # fast-engine kills are hash-decided: confirm on the
                # exact sweep, bounded to the failure prefix.  The bound
                # is the POSITIONAL op id from the kernel stats — the
                # op's "index" FIELD can differ from its position on
                # user-supplied histories, silently unbounding the sweep
                # (advisor r4).
                stop = a.get("kernel", {}).get("bar-opid")
                c = wgl_cpu.sweep_analysis(
                    self.model, history, max_configs=confirm_cap, stop_at_index=stop
                )
                path.append({"event": "confirm.sweep",
                             "outcome": _prov.verdict_str(c["valid?"])})
                if c["valid?"] is False:
                    return _fin({**a, "confirmed?": True}, "async")
                if c["valid?"] is True:
                    # hash-collision artifact: the sweep wins
                    return _fin(c, "wgl-sweep")
                break  # inconclusive: escalate to the oracles
            if "not tensorizable" in str(a.get("cause", "")):
                # no tensor form: every device rung would fail the same
                # way — the CPU oracle is the only engine
                path.append({"event": "cpu-fallback", "engine": "dfs"})
                return _fin(wgl_cpu.analysis(self.model, history), "wgl-dfs")
        if deadline is not None and deadline.expired():
            # the CPU DFS and the exact device ladder are the expensive
            # oracles; past the budget they degrade to an attributable
            # unknown instead of running unbounded
            path.append({"event": "fault.deadline", "at": "pre-oracle"})
            return _fin({
                "valid?": UNKNOWN,
                "cause": "deadline-exceeded: check budget exhausted before "
                         "the exact oracles",
            }, "competition")
        dfs = wgl_cpu.analysis(self.model, history)
        path.append({"event": "engine.dfs",
                     "outcome": _prov.verdict_str(dfs["valid?"])})
        if dfs["valid?"] != UNKNOWN:
            return _fin(dfs, "wgl-dfs")
        # the exact device engine: final refutations, quantified prefix;
        # uses its own (chunked) capacity ladder from kernel_opts
        opts = {k: v for k, v in self.kernel_opts.items()
                if k not in ("async-capacity", "confirm-max-configs")}
        path.append({"event": "route.chunked-exact"})
        a = wgl_tpu.analysis(self.model, history, deadline=deadline, **opts)
        if a["valid?"] == UNKNOWN and "not tensorizable" in str(a.get("cause", "")):
            # keep the DFS's informative unknown (budget + op)
            return _fin(dfs, "wgl-dfs")
        return _fin(a, "chunked-exact")

    @staticmethod
    def _truncate(a: Mapping) -> dict:
        out = dict(a)
        if "final-paths" in out:
            out["final-paths"] = list(out["final-paths"])[:10]
        if "configs" in out:
            out["configs"] = list(out["configs"])[:10]
        return out

    def check(self, test, history, opts):
        out = self._truncate(
            self._analyze(history, deadline=(opts or {}).get("deadline"))
        )
        if out.get("valid?") is False:
            self._render_failure(test, history, out, opts)
        _prov.emit(test, history, out, source="check", model=self.model,
                   checker="linearizable", opts=opts)
        return out

    @staticmethod
    def _render_failure(test, history, result, opts):
        """Write linear.svg next to the results — the reference renders
        the failed linearization via knossos.linear.report
        (checker.clj:207-210)."""
        from jepsen_tpu.checker.linear_svg import render_failure

        if not store.has_test_dir(test):
            return  # no store configured (bare checker unit tests)
        svg = render_failure(history, result.get("op"), result.get("cause", ""))
        try:
            d = store.test_dir(test)
            sub = (opts or {}).get("subdirectory")
            d = d / sub if sub else d
            d.mkdir(parents=True, exist_ok=True)
            (d / "linear.svg").write_text(svg)
            result["svg"] = str(d / "linear.svg")
        except OSError:
            pass  # store dir not writable

    def check_batch(self, test, histories, opts):
        """Check many subhistories in ONE vmapped kernel ladder (used by
        independent.checker: per-key shards become the batch axis —
        BASELINE config 4's shape).  CPU algorithms just loop.

        The ladder call is one ``ladder.run`` span; the evidence bundles
        are one ``checker.evidence`` span, whatever the number of
        histories."""
        if self.algorithm in ("wgl", "sweep"):
            # headless: no per-key linear.svg (they would all land on the
            # same path and overwrite each other; independent.checker
            # writes per-key artifacts itself)
            return self._evidence(
                test, histories, [self._analyze(hh) for hh in histories],
                opts)
        # kernel-opts is shaped for wgl.analysis; forward only the keys
        # batch_analysis shares (capacity ladder, rounds, exact stage).
        batch_kw = {
            k: v
            for k, v in self.kernel_opts.items()
            if k in ("capacity", "rounds", "mesh", "exact_escalation")
        }
        # Fault-tolerance keys ride the CHECKER OPTS (core.analyze fills
        # them from the test map / CLI): the ladder checkpoints into the
        # run's store dir, honors the shared deadline, and resumes when
        # asked (jepsen_tpu.parallel.batch_analysis docstring).
        opts = opts or {}
        with obs.span("ladder.run", histories=len(histories)):
            # the first check pays for importing the ladder (and JAX)
            from jepsen_tpu.parallel import batch_analysis

            results = batch_analysis(
                self.model,
                histories,
                cpu_fallback=(self.algorithm == "competition"),
                deadline=opts.get("deadline"),
                checkpoint_dir=opts.get("checkpoint-dir"),
                resume=bool(opts.get("resume?")),
                **batch_kw,
            )
        return self._evidence(test, histories, results, opts)

    def _evidence(self, test, histories, results, opts):
        """Truncate each result and build (with a store, write) its
        evidence bundle, witness walk included.  Rung admission can grow
        the result list past the input histories; bundles go to the
        caller-supplied ones (joiner verdicts are the admission hook's to
        bundle — the serving layer does so per request).  The span's
        ``fingerprint_s``, ``witness_s`` and ``digest_s`` sum those parts
        of the bundles' builds."""
        times = dict.fromkeys(_prov.BUILD_TIMES, 0.0)
        with obs.span("checker.evidence", histories=len(histories),
                      stored=store.has_test_dir(test)) as sp:
            outs = [self._truncate(r) for r in results]
            for hh, out in zip(histories, outs):
                _prov.emit(test, hh, out, source="check_batch",
                           model=self.model, checker="linearizable",
                           opts=opts, times=times)
            sp.set(**{k: round(v, 6) for k, v in times.items()})
        return outs


def linearizable(opts: Mapping) -> Checker:
    return Linearizable(opts)
