"""Batched, mesh-sharded linearizability checking.

The reference keeps per-key linearizability tractable by splitting the
workload into many small independent histories
(jepsen/src/jepsen/independent.clj:2-7, 103-238) and pmapping checkers over
them (independent.clj:285-307, checker.clj:95-97).  Here that becomes the
TPU's favourite shape: pack every history to common (B, P, G) buckets,
stack, and run ONE vmapped kernel over the batch, sharded across the mesh
on a ``histories`` axis.  Throughput scales with chips; each chip sweeps
its shard's frontiers in lockstep.
"""

from __future__ import annotations

import logging
import multiprocessing
import os
import time
from concurrent.futures import ProcessPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeout
from concurrent.futures.process import BrokenProcessPool
from pathlib import Path
from typing import Mapping, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from jepsen_tpu import _confirm_worker, faults, obs
from jepsen_tpu import models as m
from jepsen_tpu.checker import wgl_cpu
from jepsen_tpu.obs import provenance as _prov
from jepsen_tpu.ops import hashing, wgl
from jepsen_tpu.store import checkpoint as _ckpt

logger = logging.getLogger(__name__)

#: lazily created, reused across batch_analysis calls (spawn startup is
#: ~seconds; the pool is harmless idle and dies with the process).
#: Workers must never touch the accelerator — the parent owns the TPU —
#: so both the initializer and the task live in the import-light,
#: jax-free module jepsen_tpu._confirm_worker (unpickling a function
#: imports its defining module; this one would drag in the kernels).
_CONFIRM_POOL: ProcessPoolExecutor | None = None

#: one-shot flag for the exact_escalation=None behavior-change warning.
_WARNED_EXACT_DEFAULT = False

#: (step, engine, shape...) buckets already launched this process — a
#: launch whose bucket is fresh pays jit trace+compile (the runner caches
#: in ops/wgl.py key on the same step + static shapes), so its wall time
#: lands in the telemetry stage table's compile_s column (compile + first
#: execute); warm buckets land in execute_s.
_SEEN_SHAPES: set[tuple] = set()


#: dedup shapes already probed this process (the telemetry-gated
#: dedup.round probe at the end of batch_analysis): one probe per shape
#: per process — repeated ladder runs don't re-pay the probe.
_PROBED_DEDUP_SHAPES: set[tuple] = set()


#: exact-engine frontier rows per launch (sub-batch bound; see the stage
#: loop's budget comment — re-measure the true threshold on-chip).
_EXACT_LANE_BUDGET = 16 * 1024

#: async-rung frontier rows per launch: half the greedy stage's, because
#: the carried resume snapshot doubles the async kernel's resident
#: per-lane footprint (tests shrink it to force multi-chunk stages on
#: small workloads).
_CARRY_LANE_BUDGET = 32 * 1024

#: greedy-stage lanes per launch (capacity 1: one row per lane).
_GREEDY_LANE_BUDGET = 64 * 1024

#: the padded-geometry bucket tables every batched launch quantizes to
#: (P = slots, G = groups); bucket_geometry is the single source the
#: launch sites AND the serving layer's batch-compatibility key share —
#: two histories with equal bucketed geometry reuse one compiled kernel.
P_BUCKETS = (8, 16, 32, 64, 128)
G_BUCKETS = (4, 8, 16, 32, 64)

#: Continuous ladders: how long a pending member may sit skipped at
#: its rung before that rung preempts lowest-rung-first selection (see
#: the ladder loop).  A TIME bound, and generous on purpose: eager
#: preemption serves NARROW high-rung launches and costs real occupancy
#: (a skipped-launch-count bound of 8 measured 0.69-0.75 against ~0.91
#: on the round-8 acceptance demo, and even 64 still fired — rung-0
#: launches are milliseconds).  Healthy arrival streams pause well
#: inside this bound; a pathological steady stream can no longer defer
#: an escalated member's launch indefinitely.
_STARVE_SECONDS = 5.0


def bucket_geometry(B: int, P: int, G: int) -> tuple[int, int, int]:
    """The padded (B, P, G) bucket a packed history launches at."""
    return (
        wgl.pad_B(B),
        wgl._bucket(P, list(P_BUCKETS)),
        wgl._bucket(G, list(G_BUCKETS)),
    )


#: the rung capacity from which a launch pads its batch axis to its
#: live lanes alone: a lane of 512 rows or more fills the chip, so each
#: padding lane (a copy of the last live one) costs a live lane's work.
WIDE_CAPACITY = 512

#: the rung capacity from which a launch holds one lane a device: there
#: a launch's time grows about linearly with its lanes, so lanes launched
#: one after another cost no more than together, and every launch of
#: the rung runs one compiled shape whatever its lane count (a shape per
#: lane count is a compile for each new count a workload brings).
SERIAL_CAPACITY = 4096


def padded_batch(n: int, mesh: Mesh | None = None,
                 capacity: int | None = None,
                 pad_to: int | None = None) -> int:
    """The padded batch-axis size a launch of ``n`` lanes runs at: the
    next power of two, rounded up to a mesh multiple, and at least the
    pinned width ``pad_to`` (continuous batching).  Below
    ``WIDE_CAPACITY`` rows a lane (and with ``capacity=None``) the
    power of two is at least 8, so small launches share one compiled
    shape; at a wide rung it is the lanes' own.  The launch pads to
    exactly this, so the stage loop's occupancy, the exact guard and
    the serving layer's padding waste read it too."""
    floor = 0 if capacity is not None and capacity >= WIDE_CAPACITY else 3
    n_pad = 1 << max(floor, (n - 1).bit_length())
    if mesh is not None:
        shard = mesh.devices.size
        n_pad = ((n_pad + shard - 1) // shard) * shard
    if pad_to is not None and pad_to > n_pad:
        n_pad = int(pad_to)
    return n_pad


def _launch_shape(sub: Sequence[dict],
                  resumes: Sequence[tuple | None] | None = None
                  ) -> tuple[int, int, int, int | None]:
    """The bucket geometry (B, P, G) a launch of the packs ``sub``
    stacks to and, where the lanes' ``resumes`` are given (the async
    engine's: a lane resumed from ``resumes[j]`` skips its verified
    prefix), their most remaining barriers rounded up to a power of two
    >= 32, which the async tick budget reads; else None."""
    B, P, G = bucket_geometry(
        max(p["B"] for p in sub),
        max(p["P"] for p in sub),
        max(p["G"] for p in sub),
    )
    if resumes is None:
        return B, P, G, None
    rem = max(1, max(int(p["bar_active"].sum()) - (r[0] if r else 0)
                     for p, r in zip(sub, resumes)))
    return B, P, G, 1 << max(5, (rem - 1).bit_length())


def greedy_fastpath(model: m.Model, packed: Sequence[dict],
                    mesh: Mesh | None = None,
                    pad_to: int | None = None) -> list[bool]:
    """One batched greedy witness-walk launch over pre-packed histories
    — the device-batched variant of the interactive fast path (the
    CheckService serves waves with per-request host walks,
    ``wgl_cpu.greedy_walk``; this launch form is for hosts where the
    walk is kernel-bound, and pins the mesh-placement parity contract
    for greedy work).  ``packed`` entries are
    ``wgl.pack`` outputs sharing a geometry bucket; returns one flag per
    entry — True is EXACT (the walk completed: a constructive witness),
    False only means the walk stuck and the caller must escalate that
    history into the beam ladder.  Never refutes.

    The launch stacks to the same ``bucket_geometry``/``padded_batch``
    shapes the ladder's greedy rung uses, so a warm serving process
    re-hits the compiled greedy kernel instead of paying a fast-path
    compile per geometry.  With a ``mesh`` the padded batch axis is
    lane-sharded across its devices (``parallel.sharded.lane_shard``,
    ``jax.shard_map``) — placement only; flags are
    device-count independent."""
    B, P, G = bucket_geometry(
        max(p["B"] for p in packed),
        max(p["P"] for p in packed),
        max(p["G"] for p in packed),
    )
    stacked = _stack(packed, B, P, G)
    n = len(packed)
    # ``pad_to`` pins the batch axis to the caller's fixed serving
    # width: every wave size then re-hits ONE compiled greedy kernel.
    n_pad = padded_batch(n, mesh, capacity=1, pad_to=pad_to)
    n_actives = np.array([p["bar_active"].sum() for p in packed], np.int32)
    if n_pad != n:
        for k in stacked:
            if k in ("slot_lane", "slot_onehot"):
                continue
            stacked[k] = np.concatenate(
                [stacked[k]] + [stacked[k][-1:]] * (n_pad - n), axis=0
            )
        n_actives = np.concatenate(
            [n_actives, np.repeat(n_actives[-1:], n_pad - n)]
        )
    W = (P + 31) // 32
    g_args = [stacked["init_state"], jnp.asarray(n_actives)] + [
        stacked[k] for k in ASYNC_ARG_ORDER[1:]
    ]
    runner = wgl.greedy_runner(packed[0]["step"], B, P, G, W)
    if mesh is not None:
        from jepsen_tpu.parallel import sharded

        # the greedy runner's vmap batches every arg except the shared
        # slot tables (its in_axes: (0,)*14 + (None, None))
        runner = sharded.lane_shard(
            runner, mesh, n_args=len(g_args),
            replicated=(len(g_args) - 2, len(g_args) - 1), n_out=3,
        )
    finished, _stuck_at, _fired = runner(*g_args)
    return [bool(x) for x in np.asarray(finished)[:n]]


def _stays_pending(valid, fat, lossy) -> bool:
    """Whether one lane's (valid, failed_at, lossy) launch outcome leaves
    it PENDING for the next ladder rung — neither resolved True
    (survived all barriers) nor a lossless refutation.  The single
    predicate behind both the snapshot-fetch lane filter and the
    still-classification loop; keep them in sync by keeping them HERE
    (round-5 advisor: the duplicated predicate desyncs silently)."""
    if fat < 0 and valid:
        return False  # resolved True
    if fat >= 0 and not lossy:
        return False  # lossless refutation (final or confirmation-bound)
    return True


def removed_path(config: Mapping) -> str | None:
    """The removed ladder path (the "sync" engine, device-side
    confirmation, scratch-restart rungs) a recorded ladder ``config``
    pins, or None.  Such a checkpoint or evidence bundle is refused,
    never re-run under the remaining ladder as the same check; a key
    the record lacks means the remaining path."""
    if config.get("engine", "async") != "async":
        return f"engine={config['engine']!r}"
    if config.get("confirm_refutations", True) not in (True, False):
        return f"confirm_refutations={config['confirm_refutations']!r}"
    if config.get("carry_frontier", True) is not True:
        return f"carry_frontier={config['carry_frontier']!r}"
    return None


def _resolve_confirmation(res: dict, cpu_res: dict) -> dict:
    """Fold an exact-sweep confirmation verdict into the device result
    (shared by the worker confirmations and the mesh rescue).  Where the device
    refuted nothing (a lane the ladder left undecided, swept whole),
    the sweep's verdict stands."""
    if res.get("valid?") is not False:
        return cpu_res
    if cpu_res["valid?"] is False:
        return {**res, "confirmed?": True}
    if cpu_res["valid?"] is True:
        # the ~1e-13 case: a hash collision killed a live config; the
        # exact sweep's witness wins
        return cpu_res
    return {
        "valid?": "unknown",
        "cause": (
            "device refutation; exact confirmation inconclusive: "
            + str(cpu_res.get("cause", "budget exceeded"))
        ),
        "kernel": res.get("kernel"),
    }


def _default_workers(workers: int | None) -> int:
    return workers or min(8, os.cpu_count() or 1)


def _confirm_pool(workers: int | None) -> ProcessPoolExecutor:
    global _CONFIRM_POOL
    if _CONFIRM_POOL is None:
        _CONFIRM_POOL = ProcessPoolExecutor(
            max_workers=_default_workers(workers),
            mp_context=multiprocessing.get_context("spawn"),
            initializer=_confirm_worker.init,
        )
    return _CONFIRM_POOL


def _reset_confirm_pool() -> None:
    """Drop a broken pool so later calls rebuild it instead of failing."""
    global _CONFIRM_POOL
    if _CONFIRM_POOL is not None:
        _CONFIRM_POOL.shutdown(wait=False, cancel_futures=True)
        _CONFIRM_POOL = None


def warm_confirm_pool(workers: int | None = None) -> list[dict]:
    """Spawn the confirmation workers ahead of time (outside any timed
    window): pool startup + worker init cost ~seconds once per process.
    Returns each worker's ``_confirm_worker.probe_backend`` report.  A
    pool that does not come up raises (after dropping it): a run whose
    refutations cannot be confirmed must not pass for a healthy one."""
    try:
        pool = _confirm_pool(workers)
        futs = [
            pool.submit(_confirm_worker.probe_backend)
            for _ in range(_default_workers(workers))
        ]
        return [f.result() for f in futs]
    except BaseException:
        _reset_confirm_pool()
        raise


def _submit_confirmation(workers: int | None, *args):
    """Submit a confirmation, rebuilding the pool once if it is broken.
    Returns (pool, future) — the pool handle lets the resolution loop
    reset only the pool the failure actually came from — or (None, None)
    when no worker could take the job (the caller degrades that one
    history, not the batch)."""
    for _ in range(2):
        try:
            pool = _confirm_pool(workers)
            return pool, pool.submit(_confirm_worker.confirm_refutation, *args)
        except BrokenProcessPool:
            _reset_confirm_pool()
    return None, None


def _device_oom_spiller(ctx) -> bool:
    """The default OOM spiller (faults.register_oom_spiller): evict the
    cached jitted runners so the backend can release their executables'
    device buffers, then collect.  Only on non-CPU backends — the CPU
    backend has no allocator pressure worth a recompile, and evicting
    the process-shared runner caches there would just slow every later
    ladder (the tier-1 suite shares them)."""
    try:
        if jax.default_backend() == "cpu":
            return False
    except Exception:  # noqa: BLE001 — no backend: nothing to free
        return False
    n = wgl.evict_runner_caches()
    import gc

    gc.collect()
    return n > 0


faults.register_oom_spiller(_device_oom_spiller)


def make_mesh(n_devices: int | None = None, axis: str = "histories") -> Mesh:
    """A 1-D device mesh over the first ``n_devices`` devices."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def mesh_device_ids(mesh: Mesh | None) -> list[int]:
    """The device ids a placement launches on: the mesh's members in
    lane order, or ``[0]`` (jax's default device) single-device — the
    ONE definition behind every device-attribution site (ladder/launch
    span attrs, the lane-shard wrapper, the serve bubble gauge)."""
    if mesh is None:
        return [0]
    return [int(d.id) for d in mesh.devices.ravel().tolist()]


def _stack(packs: list[dict], B: int, P: int, G: int) -> dict:
    padded = [wgl.pad_packed(p, B=B, P=P, G=G) for p in packs]
    out = {}
    out["init_state"] = np.stack([p["init_state"] for p in padded])
    out["bar_active"] = np.stack([p["bar_active"] for p in padded])
    for i, name in enumerate(["bar_f", "bar_v1", "bar_v2", "bar_slot"]):
        out[name] = np.stack([p["bar"][i] for p in padded])
    for i, name in enumerate(["mov_f", "mov_v1", "mov_v2", "mov_open"]):
        out[name] = np.stack([p["mov"][i] for p in padded])
    for i, name in enumerate(["grp_f", "grp_v1", "grp_v2"]):
        out[name] = np.stack([p["grp"][i] for p in padded])
    out["grp_open"] = np.stack([p["grp_open"] for p in padded])
    out["slot_lane"] = padded[0]["slot_lane"]
    out["slot_onehot"] = padded[0]["slot_onehot"]
    return out


_ARG_ORDER = [
    "init_state", "bar_active", "bar_f", "bar_v1", "bar_v2", "bar_slot",
    "mov_f", "mov_v1", "mov_v2", "mov_open",
    "grp_f", "grp_v1", "grp_v2", "grp_open",
    "slot_lane", "slot_onehot",
]

#: the async kernel replaces bar_active with a per-history n_active scalar
#: (inserted after init_state at the call site).
ASYNC_ARG_ORDER = [k for k in _ARG_ORDER if k != "bar_active"]


def batch_analysis(
    model: m.Model,
    histories: Sequence[Sequence[dict]],
    capacity: int | Sequence[int] = (64, 512, 4096),
    rounds: int = 8,
    mesh: Mesh | None = None,
    cpu_fallback: bool = True,
    exact_escalation: Sequence[int] | None = None,
    confirm_refutations: bool = True,
    confirm_workers: int | None = None,
    confirm_max_configs: int = 2_000_000,
    greedy_first: bool = True,
    dedup_backend: str | None = None,
    checkpoint_dir: str | Path | None = None,
    resume: bool = False,
    deadline=None,
    admission=None,
    frontier_budget_mb: float | None = None,
) -> list[dict]:
    """Check many histories against one model in batched kernel launches.

    The ladder: with ``greedy_first`` (default) a capacity-1 greedy
    witness walk (wgl.greedy_runner), where most VALID lanes resolve
    for one buffer-free scan and nothing is refuted; then the async
    rungs of ``capacity`` (wgl.async_runner, lanes step barriers
    asynchronously within a tick budget, wgl.async_ticks); then the
    exact rungs of ``exact_escalation`` (frontier_update, ~10x slower
    per lane but content-exact, closure depth bounded by ``rounds``;
    ``None`` means none).  Each rung re-batches only the still-undecided
    histories, padded to a power of two so compiles are reused, and
    wide rungs sub-batch.  An async rung RESUMES each straggler from the
    exact pre-loss frontier the previous async rung saved at its failure
    barrier: the verified prefix is never re-paid, and the tick budget
    shrinks to the max REMAINING barriers.

    ``True`` verdicts are sound from every stage (a surviving frontier is
    a constructive witness), and exact refutations are final.  Async
    refutations are decided on 64-bit row hashes, so with
    ``confirm_refutations`` (the default, the "never an unconfirmed
    False" contract) each is confirmed by the exact CPU config-set sweep
    in worker processes, CONCURRENTLY with the remaining rungs, so
    soundness costs almost no wall clock.  A sweep past
    ``confirm_max_configs`` leaves "unknown" (never a wrong False); a
    sweep that disagrees (the ~1e-13 collision case) wins.  Remaining
    unknowns fall back to the CPU config-set sweep when ``cpu_fallback``:
    a history the last rung leaves lossy is swept whole in the
    confirmation workers as it leaves the ladder when refutations are
    confirmed there (and no mesh rescue runs); the rest in this process
    after the ladder.  Returns one knossos-shaped result per history,
    in order.

    ``dedup_backend`` selects the per-round frontier dedup backend for
    every rung — "sort" (multi-key hash sort) or "bucket" (packed radix
    buckets; see jepsen_tpu.ops.hashing).  None resolves through the
    JEPSEN_TPU_DEDUP_BACKEND env var, then the module default.  Verdict
    semantics are backend-independent: async refutations are
    hash-decided (and confirmed) either way, exact-engine kills are
    content-decided either way.  (The greedy rung walks a single
    configuration — no frontier, nothing to dedup — so the backend
    choice is moot there by construction.)

    Fault tolerance (jepsen_tpu.faults): every device launch runs under
    a retry policy — transient ``XlaRuntimeError``s retry with
    exponential backoff; ``RESOURCE_EXHAUSTED`` halves the sub-batch
    (and the stage lane budget) and relaunches, floor one lane; a
    launch that still fails degrades ONLY its lanes to ``"unknown"``
    with a ``cause`` naming the error, never the whole batch.
    ``checkpoint_dir`` persists the ladder's durable state after every
    stage (jepsen_tpu.store.checkpoint: verdicts so far, the pending
    set, resume frontiers, in-flight confirmation descriptors, the
    RNG-free config); ``resume=True`` reloads it and re-enters the
    ladder at the saved rung — a kill -9 mid-ladder then a resume
    yields verdicts identical to an uninterrupted run.  On resume the
    SAVED config wins over the caller's ladder arguments (verdict
    identity requires the original ladder), and a checkpoint whose
    history fingerprint doesn't match, or whose config pins a removed
    ladder path (``removed_path``), is quarantined and the run starts
    fresh.
    ``deadline`` (seconds or a faults.Deadline) bounds wall clock: it
    is polled at stage boundaries; on expiry the ladder checkpoints,
    marks the remaining packs ``unknown`` with cause
    ``deadline-exceeded`` plus a pointer to the checkpoint, and still
    returns a complete result list.

    Bounded memory (round 8): an OOM first tries the registered
    device-memory spillers (``faults.try_oom_spill`` — runner-cache
    eviction on real accelerators) and retries the SAME launch before
    any lane halving, so the sub-batch ladder engages only once spill
    is exhausted.  ``frontier_budget_mb`` (or the
    JEPSEN_TPU_FRONTIER_BUDGET_MB env var) caps the exact engine's
    device frontier working set: the chunked exact path (unsafe-shape
    lanes) then host-spills overflow
    rows instead of going lossy (``ops.wgl.chunked_analysis``), and a
    history fixed memory still cannot decide returns ``unknown`` with a
    machine-readable undecidability report in its ``cause``
    (``ops.spill.undecidability_report``) — never a bare unknown.

    Continuous batching (``admission``): an object with a
    ``poll(stage=, lanes=)`` method is consulted at every rung boundary
    and may return new histories that JOIN the running ladder — they
    are packed, enter at rung 0 (the greedy walk), and run the same
    rung sequence a one-shot call would, so verdict semantics are
    identical; their results are appended to the returned list in
    admission order (index = ``len(histories)`` at the moment of the
    poll — the caller mirrors that counter to demux).  Lane slots
    recycle naturally: resolved members leave the pending set at the
    same boundaries joiners enter, which is what keeps device occupancy
    high under open arrival (streaming batched beam search,
    arXiv:2010.02164).  Optional hook methods: ``on_result(i, result)``
    is called the moment history ``i``'s verdict is DECIDED (True, or a
    final/confirmed False) so a serving layer can resolve that caller
    mid-ladder; ``on_rung(stage=, engine=, capacity=, lanes=, padded=,
    seconds=)`` reports, after each rung's launches complete, the live
    lanes, the padded lane-slots actually launched, and the rung's
    launch seconds — compile + execute device time, not the stage wall
    — for device-time-weighted occupancy.  A hook may also advertise
    ``pad_lanes``: every rung launch is then padded UP to that fixed
    batch axis (clamped to the stage lane budget), so membership churn
    never changes the compiled kernel shape mid-service.  With an
    admission hook, finished worker confirmations are also drained at
    rung boundaries (refuted requests resolve while the ladder keeps
    running).  The ladder returns when the pending set is empty and a
    poll returned no joiners.
    """
    dedup = hashing.resolve_dedup_backend(dedup_backend)
    histories = list(histories)
    results: list[dict | None] = [None] * len(histories)
    packs: list[dict] = []
    idxs: list[int] = []
    t_pack = time.perf_counter()
    for i, hist in enumerate(histories):
        try:
            p = wgl.pack(model, hist)
        except wgl.NotTensorizable as e:
            results[i] = {"valid?": "unknown", "cause": f"not tensorizable: {e}"}
            continue
        if p["B"] == 0:
            results[i] = {"valid?": True}
        else:
            packs.append(p)
            idxs.append(i)
    obs.span_event(
        "ladder.pack", time.perf_counter() - t_pack,
        histories=len(histories), tensorizable=len(packs),
    )

    if not isinstance(confirm_refutations, bool):
        raise ValueError(
            f"confirm_refutations must be a bool, got {confirm_refutations!r}"
        )
    capacities = [capacity] if isinstance(capacity, int) else list(capacity)
    batch_caps = [int(c) for c in capacities]
    if exact_escalation is None and not cpu_fallback:
        # Without the CPU fallback a capacity-bound history stays
        # "unknown" with no other runtime signal: say so once.
        global _WARNED_EXACT_DEFAULT
        if not _WARNED_EXACT_DEFAULT:
            _WARNED_EXACT_DEFAULT = True
            import warnings

            warnings.warn(
                "exact_escalation=None now means NO exact stages (it "
                "used to mean one at 4x the last batch capacity); with "
                "cpu_fallback=False, capacity-bound histories stay "
                "'unknown'. Pass exact_escalation=() to silence, or an "
                "explicit ladder to restore exact stages.",
                stacklevel=2,
            )
    exact_caps = [int(c) for c in (exact_escalation or ())]

    # ------------------------------------------------------------------
    # Checkpoint / resume (jepsen_tpu.store.checkpoint).
    # ------------------------------------------------------------------
    checkpoint_dir = Path(checkpoint_dir) if checkpoint_dir is not None else None
    deadline = faults.Deadline.coerce(deadline)
    deadline_tripped = False
    trip_checkpointed = False  # a resumable trip checkpoint is on disk
    fp_dirty = False  # rung admission grew `histories` since fp was taken
    no_fallback: set[int] = set()  # history idxs the CPU fallback must skip
    start_stage = 0
    restored = None
    fp = None
    if checkpoint_dir is not None or resume:
        fp = _ckpt.fingerprint(histories)
    if resume and checkpoint_dir is not None and _ckpt.exists(checkpoint_dir):
        t_load = time.perf_counter()
        try:
            restored = _ckpt.load(checkpoint_dir)
        except _ckpt.CheckpointError as e:
            # Corrupt pairs were already quarantined aside by the
            # durable layer (with the machine-readable report on
            # e.report); the fresh run below reproduces uninterrupted
            # verdicts, so this degradation is total-recovery.
            logger.warning("unreadable checkpoint in %s (%s); running fresh",
                           checkpoint_dir, e)
            obs.counter("fault.checkpoint.mismatch",
                        reason=getattr(e, "report", None) and
                        e.report.get("reason") or "unreadable",
                        report=getattr(e, "report", None))
        why = None
        if restored is not None:
            removed = removed_path(restored["config"])
            if restored["config"].get("fingerprint") != fp:
                # resuming against changed inputs could only produce
                # wrong verdicts
                reason, why = "fingerprint", "was written for other histories"
            elif removed is not None:
                reason, why = "removed-path", f"pins a removed path ({removed})"
        if why is not None:
            # The stale pair is QUARANTINED aside, not merely ignored: a
            # later --resume against the same dir (now with the matching
            # histories again) must never pick the mismatched state back
            # up, and the checkpoint this fresh run is about to write
            # must not interleave with the old files.
            quarantined = _ckpt.quarantine(checkpoint_dir,
                                           reason=f"stale-{reason}")
            logger.warning(
                "checkpoint in %s %s; running fresh; stale files "
                "quarantined: %s", checkpoint_dir, why, quarantined)
            obs.counter("fault.checkpoint.quarantined",
                        reason=reason, files=quarantined)
            obs.counter("fault.checkpoint.mismatch", reason=reason)
            restored = None
        if restored is not None:
            # The saved config wins: verdict identity requires the
            # original ladder, and the CLI resume path can't know the
            # original kwargs.
            cfg = restored["config"]
            batch_caps = [int(c) for c in cfg.get("capacity", batch_caps)]
            exact_caps = [int(c) for c in cfg.get("exact_escalation", exact_caps)]
            rounds = int(cfg.get("rounds", rounds))
            greedy_first = bool(cfg.get("greedy_first", greedy_first))
            dedup = cfg.get("dedup", dedup)
            confirm_refutations = cfg.get(
                "confirm_refutations", confirm_refutations)
            frontier_budget_mb = cfg.get(
                "frontier_budget_mb", frontier_budget_mb)
            start_stage = int(restored["stage"])
            obs.span_event(
                "fault.checkpoint.load", time.perf_counter() - t_load,
                stage=start_stage, pending=len(restored["pending"]),
                complete=restored["complete"],
            )
            if restored["complete"]:
                # A finished run's checkpoint: hand back the saved
                # verdicts (idempotent resume; no device work at all).
                # Each verdict's provenance records the restore — a
                # replayed/resumed answer is a different trust path
                # than a fresh device run.
                for i, r in restored["results"].items():
                    if 0 <= i < len(results):
                        if isinstance(r, dict):
                            _prov.attach(
                                r,
                                [{"event": "checkpoint.restored",
                                  "complete": True, "stage": start_stage}],
                                engine={"engine": "async",
                                        "dedup_backend": dedup},
                            )
                        results[i] = r
                return [r if r is not None else {"valid?": "unknown"}
                        for r in results]
    # "engine" and "carry_frontier" are constants, recorded so records
    # keep their bytes and removed_path can tell older ones apart
    config = {
        "engine": "async", "capacity": list(batch_caps),
        "exact_escalation": list(exact_caps), "rounds": int(rounds),
        "greedy_first": bool(greedy_first),
        "carry_frontier": True, "dedup": dedup,
        "confirm_refutations": confirm_refutations, "fingerprint": fp,
        "frontier_budget_mb": frontier_budget_mb,
    }

    # ------------------------------------------------------------------
    # Verdict provenance (obs.provenance): a bounded per-history
    # decision-path trail, attached to every result before it leaves the
    # ladder (both the early _notify demux and the final return), so the
    # caller can emit an evidence bundle recording exactly which rungs,
    # fallbacks, and fault events produced each verdict.
    # ------------------------------------------------------------------
    prov_cfg = {k: v for k, v in config.items() if k != "fingerprint"}
    prov_engine: dict = {"engine": "async", "dedup_backend": dedup,
                        "greedy_first": bool(greedy_first)}
    if dedup == "pallas":
        try:
            from jepsen_tpu.ops import wide_kernel as _wkp

            prov_engine["pallas_interpret"] = bool(_wkp.interpret_default())
        except Exception:  # noqa: BLE001 — provenance must not lose ladders
            pass
    prov_paths: dict[int, list] = {}

    def _pv(i: int, event: str, **attrs) -> None:
        lst = prov_paths.setdefault(i, [])
        if len(lst) < _prov.MAX_PATH:
            lst.append({"event": event, **attrs})

    def _pv_merge(i: int, sub: dict | None) -> None:
        """Fold a nested engine's provenance (chunked_analysis) into
        this history's trail: the ladder's events stay first, the inner
        trajectory follows."""
        if not sub:
            return
        eng = sub.get("engine")
        if eng:
            _pv(i, "engine.nested", **eng)
        lst = prov_paths.setdefault(i, [])
        for e in sub.get("path", ()):
            if len(lst) >= _prov.MAX_PATH:
                break
            lst.append(dict(e))

    def _attach_prov(i: int) -> None:
        r = results[i]
        if isinstance(r, dict):
            _prov.attach(r, prov_paths.get(i, []), engine=prov_engine,
                         config=prov_cfg)

    def _notify(i: int) -> None:
        """Early per-history demux for the rung-admission caller: hand a
        DECIDED verdict (True, or a final False) to the hook the moment
        it is final, instead of at return.  Unknowns are left for the
        return path — some are provisional (the CPU fallback may still
        decide them), and the caller settles every leftover from the
        returned list anyway."""
        if admission is None:
            return
        r = results[i]
        if r is None or r.get("valid?") == "unknown":
            return
        _attach_prov(i)
        try:
            admission.on_result(i, r)
        except Exception:  # noqa: BLE001 — a broken feeder must not
            # lose the ladder; the verdict still lands in the return list
            logger.exception("rung-admission on_result failed (history %d)", i)

    if restored is not None:
        # mid-run resume: every history's trail records that this run
        # continued from a checkpoint rather than starting fresh
        for _pi in range(len(histories)):
            _pv(_pi, "checkpoint.restored", stage=start_stage)

    #: device ids every launch of this ladder runs on (lane-sharded
    #: over the mesh, or jax's default device) — the device-attribution
    #: attr on ladder.launch/ladder.stage spans that obs.critpath's
    #: per-device timeline and the Perfetto device lanes read.
    _dev_ids = mesh_device_ids(mesh)

    #: per-stage launch accounting for the telemetry stage table; "_key"
    #: is the launched (engine, shape) bucket, set at each runner site.
    launch_acc: dict = {}

    def _reset_launch_acc() -> None:
        launch_acc.update(
            launches=0, compile_launches=0, compile_s=0.0, execute_s=0.0,
            device_bytes_peak=0,
        )

    _reset_launch_acc()

    def _launch(st_engine: str, batch_cap: int, sub: list[dict],
                sub_resumes: list[tuple | None] | None = None,
                pad_to: int | None = None, retry: bool = False,
                shape: tuple | None = None):
        """Instrumented wrapper over the kernel launch: times the launch,
        classifies it compile (fresh shape bucket) vs execute, samples
        the post-launch device-buffer footprint (the stage's memory
        high-water mark), and emits a ladder.launch telemetry span.
        ``retry`` marks a reduced-size OOM-halved / spill-retry launch —
        excluded from the watchdog's launch-time EWMA baseline
        (faults.record_launch_seconds).  The span's ``padded`` is the
        lane slots launched: ``lanes`` and their padding copies.
        ``shape`` is _launch_impl's."""
        n_pad = padded_batch(len(sub), mesh, batch_cap, pad_to)
        with obs.span(
            "ladder.launch", engine=st_engine, capacity=batch_cap,
            lanes=len(sub), padded=n_pad, devices=_dev_ids,
        ) as sp:
            t0 = time.perf_counter()
            out = _launch_impl(st_engine, batch_cap, sub, sub_resumes, n_pad,
                               shape)
            dt = time.perf_counter() - t0
            # Feed the process launch-time EWMA the serving layer's
            # hung-launch watchdog derives its wall-clock caps from
            # (reduced retry launches are tagged out of the baseline).
            faults.record_launch_seconds(dt, retry=retry)
            key = launch_acc.pop("_key", None)
            compiled = key is not None and key not in _SEEN_SHAPES
            if key is not None:
                _SEEN_SHAPES.add(key)
            launch_acc["launches"] += 1
            if compiled:
                launch_acc["compile_launches"] += 1
                launch_acc["compile_s"] += dt
            else:
                launch_acc["execute_s"] += dt
            obs.counter(
                "ladder.compile_cache.miss" if compiled
                else "ladder.compile_cache.hit",
                engine=st_engine,
            )
            sp.set(compiled=compiled)
            if obs.observing():
                # Post-launch device footprint: right after a launch is
                # where the stage's buffers (frontier, snapshot, sort
                # scratch) peak host-visibly — the per-stage high-water
                # mark in the telemetry stage table.
                db = wgl.device_buffer_bytes()
                if db is not None and db > launch_acc["device_bytes_peak"]:
                    launch_acc["device_bytes_peak"] = db
        return out

    def _launch_impl(st_engine: str, batch_cap: int, sub: list[dict],
                     sub_resumes: list[tuple | None] | None, n_pad: int,
                     shape: tuple | None = None):
        """Stack ``sub`` to common bucket shapes, pad the batch axis to
        ``n_pad`` lanes (``padded_batch``) and run one vmapped kernel
        launch.  ``shape``, where given, is ``_launch_shape`` of the
        rung's whole group: a part of it then runs the compiled shape
        and tick budget the whole group's launch would have, so its
        lanes' outcomes are the same.  Returns (valid, failed_at, lossy,
        peak, snap) with host arrays of len(sub).  ``sub_resumes[j]``
        optionally carries lane j's saved (bsnap, state, fok, fcr,
        alive) frontier from the previous rung — the lane resumes there
        instead of re-running the whole history.  ``snap`` is the async
        engine's resume snapshot as ON-DEVICE arrays (bsnap, state, fok,
        fcr, alive), or None: the stage loop fetches rows host-side only for
        lanes that actually stay pending AND have a later async rung to
        resume on — each ``np.asarray`` here is a device-to-host
        round-trip, and fetching every lane's full padded frontier after
        every rung was measured at ~0.8 s on the bench ladder (chip
        ablation, round 5)."""
        B, P, G, b_rem = shape or _launch_shape(sub, sub_resumes)
        stacked = _stack(sub, B, P, G)
        n = len(sub)
        # Padding lanes repeat the last live lane and are sliced off.
        # Narrow rungs pad to a power of two >= 8 so the vmapped kernel
        # compiles once per bucket, not once per batch size; wide rungs
        # pad to their lanes' own power of two, as there each copy costs
        # a live lane's work.  A pinned ``pad_to`` (continuous batching)
        # widens every rung of a served ladder to one fixed batch axis,
        # so membership churn (joiners, resolved lanes) never changes
        # the compiled shape mid-service.
        if n_pad != n:
            for k in stacked:
                if k in ("slot_lane", "slot_onehot"):
                    continue
                reps = np.concatenate(
                    [stacked[k]] + [stacked[k][-1:]] * (n_pad - n), axis=0
                )
                stacked[k] = reps
        args = [stacked[k] for k in _ARG_ORDER]
        if mesh is not None:
            axis = mesh.axis_names[0]
            spec = NamedSharding(mesh, PartitionSpec(axis))
            rep = NamedSharding(mesh, PartitionSpec())
            args = [
                jax.device_put(a, rep if k in ("slot_lane", "slot_onehot") else spec)
                for k, a in zip(_ARG_ORDER, args)
            ]
        W = (P + 31) // 32
        snap = None
        if st_engine == "greedy":
            # Stage 0: the capacity-1 greedy witness walk — resolves most
            # VALID lanes for ~nothing (no frontier buffers, one scan).
            # Never refutes: unresolved lanes report lossy so the stage
            # loop keeps them pending for the beam ladder.
            n_actives = np.array([p["bar_active"].sum() for p in sub], np.int32)
            if n_pad != n:
                n_actives = np.concatenate([n_actives, np.repeat(n_actives[-1:], n_pad - n)])
            by_name = dict(zip(_ARG_ORDER, args))
            # init_state is already stacked/padded/mesh-sharded in args
            g_args = [by_name["init_state"], jnp.asarray(n_actives)] + [
                by_name[k] for k in ASYNC_ARG_ORDER[1:]
            ]
            if mesh is not None:
                axis = mesh.axis_names[0]
                spec = NamedSharding(mesh, PartitionSpec(axis))
                g_args[1] = jax.device_put(np.asarray(g_args[1]), spec)
            launch_acc["_key"] = (sub[0]["step"], "greedy", B, P, G, W, n_pad)
            runner = wgl.greedy_runner(sub[0]["step"], B, P, G, W)
            finished, _stuck_at, _fired = runner(*g_args)
            finished = np.asarray(finished)[:n]
            return (
                finished,
                np.full(n, -1, np.int32),
                ~finished,  # unresolved = lossy -> stays pending
                np.ones(n, np.int32),
                snap,
            )
        if st_engine == "async":
            n_actives = np.array([p["bar_active"].sum() for p in sub], np.int32)
            # Per-lane resume frontiers: fresh single-config at barrier 0,
            # or the saved snapshot re-bucketed to this stage's shapes.
            F = batch_cap
            bptr0, st0, fo0, fc0, al0 = wgl.fresh_frontier(
                n, F, W, G, [p["init_state"] for p in sub]
            )
            if sub_resumes is not None:
                for j, r in enumerate(sub_resumes):
                    if r is None:
                        continue
                    bs, rst, rfo, rfc, ral = wgl.pad_resume(r, F, W, G)
                    bptr0[j], st0[j], fo0[j], fc0[j], al0[j] = bs, rst, rfo, rfc, ral
            # Tick budget from the MAX REMAINING barriers, not the padded
            # B: resumed lanes skip their verified prefix, so the budget
            # (and the stage's worst-case wall clock) shrinks with it.
            T = wgl.async_ticks(min(b_rem, B), batch_cap)
            if n_pad != n:
                n_actives = np.concatenate([n_actives, np.repeat(n_actives[-1:], n_pad - n)])
                reps = n_pad - n
                bptr0 = np.concatenate([bptr0, np.repeat(bptr0[-1:], reps)])
                st0 = np.concatenate([st0, np.repeat(st0[-1:], reps, axis=0)])
                fo0 = np.concatenate([fo0, np.repeat(fo0[-1:], reps, axis=0)])
                fc0 = np.concatenate([fc0, np.repeat(fc0[-1:], reps, axis=0)])
                al0 = np.concatenate([al0, np.repeat(al0[-1:], reps, axis=0)])
            order = ASYNC_ARG_ORDER
            by_name = dict(zip(_ARG_ORDER, args))
            a_args = [jnp.asarray(bptr0), jnp.asarray(st0), jnp.asarray(fo0),
                      jnp.asarray(fc0), jnp.asarray(al0),
                      jnp.asarray(n_actives)] + [by_name[k] for k in order[1:]]
            if mesh is not None:
                axis = mesh.axis_names[0]
                spec = NamedSharding(mesh, PartitionSpec(axis))
                for ai in range(6):
                    a_args[ai] = jax.device_put(np.asarray(a_args[ai]), spec)
            launch_acc["_key"] = (sub[0]["step"], "async", batch_cap, T, B, P, G, W, n_pad, dedup)
            runner = wgl.async_runner(sub[0]["step"], batch_cap, T, B, P, G, W, dedup)
            valid, failed_at, lossy, peak, bsnap, sst, sfo, sfc, sal = runner(*a_args)
            # keep the snapshot ON-DEVICE; the stage loop fetches only
            # the still-pending rows (and only when a later async rung
            # exists to resume on)
            snap = (bsnap, sst, sfo, sfc, sal)
        else:  # "exact": content-compare dedup/domination — may refute
            launch_acc["_key"] = (sub[0]["step"], "exact", batch_cap, int(rounds), B, P, G, W, n_pad, dedup)
            runner = wgl.exact_batched_runner(sub[0]["step"], batch_cap, int(rounds), P, G, W, dedup)
            valid, failed_at, lossy, peak = runner(*args)
        return (
            np.asarray(valid)[:n],
            np.asarray(failed_at)[:n],
            np.asarray(lossy)[:n],
            np.asarray(peak)[:n],
            snap,
        )

    def _emit_stage(t_stage: float, stage_attrs: dict, **extra) -> None:
        """One ladder.stage telemetry span per rung: wall time, lanes in,
        verdict counts, the stage's compile/execute launch split, and
        its device-memory high-water mark."""
        mem = {}
        if launch_acc.get("device_bytes_peak"):
            mem["device_bytes_peak"] = launch_acc["device_bytes_peak"]
            obs.gauge("device.buffer_bytes", launch_acc["device_bytes_peak"],
                      at="ladder-stage", stage=stage_attrs.get("stage"))
        obs.span_event(
            "ladder.stage", time.perf_counter() - t_stage,
            devices=_dev_ids,
            launches=launch_acc["launches"],
            compile_launches=launch_acc["compile_launches"],
            compile_s=round(launch_acc["compile_s"], 6),
            execute_s=round(launch_acc["execute_s"], 6),
            **mem, **stage_attrs, **extra,
        )

    stages = [("async", c) for c in batch_caps] + [("exact", c) for c in exact_caps]
    if greedy_first and stages:
        stages = [("greedy", 1)] + stages
    pending = list(range(len(packs)))
    resumes: dict[int, tuple] = {}  # pack idx -> saved resume frontier
    # hist idx -> (pool, future, device result, t, op_pos, obs.Ctx): the
    # Ctx is the span context captured at SUBMIT time, re-attached when
    # the drain resolves the confirmation — trace ids survive the
    # worker-pool process boundary (the worker itself records nothing;
    # its submit/resolve bracket in this process carries the trace).
    confirm_futs: dict = {}
    confirm_degraded: set[int] = set()  # hist idxs whose confirmation hit the deadline
    if restored is not None:
        # Re-enter the ladder where the checkpoint left it: verdicts so
        # far (including the pending lanes' unknown placeholders), the
        # pending set, and each pending lane's carried-frontier resume
        # snapshot.  In-flight worker confirmations are RESUBMITTED (the
        # old futures died with the old process).
        pack_of = {i: k for k, i in enumerate(idxs)}
        for i, r in restored["results"].items():
            if 0 <= i < len(results):
                results[i] = r
        pending = [pack_of[i] for i in restored["pending"] if i in pack_of]
        for i, fr in restored["resumes"].items():
            if i in pack_of:
                resumes[pack_of[i]] = fr
        for i, info in restored["confirms"].items():
            # no failure barrier: an undecided lane swept whole
            op_pos = None if info["op_pos"] is None else int(info["op_pos"])
            pool, fut = _submit_confirmation(
                confirm_workers, model, list(histories[i]),
                confirm_max_configs, op_pos,
            )
            obs.counter("confirm.submitted")
            confirm_futs[i] = (
                pool, fut, info["res"], time.perf_counter(), op_pos,
                obs.capture(),
            )
            results[i] = info["res"]

    #: per-pack rung cursor: stages[rungs[k]] is pack k's NEXT rung.
    #: Every initial pack starts (or resumes) at the same rung, so
    #: without rung-boundary admission the loop below walks the ladder
    #: exactly like a uniform per-stage loop; packs admitted mid-ladder
    #: enter at rung 0 and catch up, running the SAME rung sequence a
    #: one-shot call would (continuous batching changes who shares a
    #: launch, never how a history is decided).
    rungs: dict[int, int] = {k: start_stage for k in pending}
    if restored is not None and restored.get("rungs"):
        pack_of = {i: k for k, i in enumerate(idxs)}
        for i, r in restored["rungs"].items():
            if i in pack_of:
                rungs[pack_of[i]] = int(r)

    def _save_checkpoint(next_stage: int, complete: bool = False):
        """Persist the ladder's durable state at a stage boundary; a
        save failure is logged, counted, and never fails the analysis
        (the checkpoint is a recovery aid, not a verdict input)."""
        if checkpoint_dir is None:
            return None
        nonlocal fp_dirty
        if fp_dirty:
            # Rung admission grew the membership since the fingerprint
            # was taken: re-fingerprint the CURRENT histories so a
            # resume over the drained member list (original + joined)
            # matches instead of spuriously running fresh.
            config["fingerprint"] = _ckpt.fingerprint(histories)
            fp_dirty = False
        t0 = time.perf_counter()
        try:
            path = _ckpt.save(
                checkpoint_dir,
                config=config,
                stage=next_stage,
                results={i: r for i, r in enumerate(results) if r is not None},
                pending=[idxs[k] for k in pending],
                confirms={
                    i: {"res": res, "op_pos": op_pos}
                    for i, (_p, _f, res, _t, op_pos, _c) in confirm_futs.items()
                },
                resumes={idxs[k]: resumes[k] for k in pending if k in resumes},
                rungs={idxs[k]: rungs.get(k, next_stage) for k in pending},
                complete=complete,
            )
        except Exception:  # noqa: BLE001 — see docstring
            logger.warning("couldn't write checker checkpoint to %s",
                           checkpoint_dir, exc_info=True)
            obs.counter("fault.checkpoint.error")
            return None
        obs.span_event(
            "fault.checkpoint.save", time.perf_counter() - t0,
            stage=next_stage, pending=len(pending), complete=complete,
        )
        return path

    early_confirmed: set[int] = set()  # resolved at a rung boundary

    def _poll_confirmations() -> None:
        """Rung-boundary confirmation demux (continuous batching): a
        worker sweep that already finished resolves NOW — its caller's
        future settles while the ladder keeps running — instead of at
        the final drain.  Only the clean success path resolves here;
        failed/timed-out futures keep their descriptor so the final
        drain's full retry machinery (pool rebuild, bounded resubmit,
        deadline grace) handles them unchanged."""
        done = [
            i for i, e in confirm_futs.items()
            if e[1] is not None and e[1].done()
            and not e[1].cancelled() and e[1].exception() is None
        ]
        for i in done:
            _pool, fut, dev_res, t_submit, _op_pos, ctx = confirm_futs.pop(i)
            early_confirmed.add(i)
            with obs.attach(ctx):
                obs.gauge(
                    "confirm.queue_latency_s",
                    round(time.perf_counter() - t_submit, 6), history=i,
                )
                results[i] = _resolve_confirmation(dev_res, fut.result())
            _pv(i, "confirm.resolved", mode="worker",
                outcome=_prov.verdict_str(results[i].get("valid?")))
            _notify(i)

    def _poll_admission() -> None:
        """The rung-boundary admission hook (continuous batching): ask
        the caller for new histories to JOIN the running ladder.  Each
        joiner packs here, enters the pending set at rung 0, and is
        assigned result index len(histories) — sequential, so the
        caller can mirror the counter to demux.  A broken hook degrades
        to no joiners, never a lost ladder."""
        nonlocal fp_dirty
        if admission is None:
            return
        min_rung = min((rungs[k] for k in pending), default=0)
        try:
            new_hists = admission.poll(stage=min_rung, lanes=len(pending))
        except Exception:  # noqa: BLE001 — see docstring
            logger.exception(
                "rung-admission poll failed; continuing without joiners")
            new_hists = None
        for hist in new_hists or ():
            i = len(histories)
            histories.append(list(hist))
            results.append(None)
            fp_dirty = True
            try:
                p = wgl.pack(model, histories[i])
            except wgl.NotTensorizable as e:
                results[i] = {
                    "valid?": "unknown", "cause": f"not tensorizable: {e}"}
                continue
            if p["B"] == 0:
                results[i] = {"valid?": True}
                _notify(i)
                continue
            k = len(packs)
            packs.append(p)
            idxs.append(i)
            pending.append(k)
            rungs[k] = 0
            _pv(i, "admission.joined", at_stage=min_rung)
            obs.counter("ladder.rung_admission", stage=min_rung)

    #: Continuous batching pins every rung launch to one fixed batch
    #: axis (the hook advertises its width): joiners and resolved lanes
    #: then recycle slots inside a single compiled shape instead of
    #: walking the ladder through a fresh XLA compile per membership
    #: size (a mid-service async compile measured ~2.5 s on CPU — worse
    #: than the batch it served).
    pad_lanes = getattr(admission, "pad_lanes", None)
    pad_lanes = int(pad_lanes) if pad_lanes else None

    def _serial(batch_cap: int) -> bool:
        """Whether the rung launches one lane a device at a time
        (SERIAL_CAPACITY); not where a pinned width makes every launch
        that wide anyway."""
        return batch_cap >= SERIAL_CAPACITY and pad_lanes is None

    def _lanes_per_launch(budget_rows: float, batch_cap: int) -> int:
        """The most lanes one launch of a rung holds: the frontier-row
        budget's share, and on a serial rung one a device."""
        lanes = max(1, int(budget_rows) // batch_cap)
        if _serial(batch_cap):
            lanes = min(lanes, mesh.devices.size if mesh is not None else 1)
        return lanes

    def _pinned_width(lanes_cap: int) -> int | None:
        """A launch's pinned batch axis: the fixed width clamped to the
        stage's chunk lane budget, so pinning the shape never exceeds
        the resident-row bound; None where nothing pins it."""
        if pad_lanes is None:
            return None
        return min(pad_lanes, padded_batch(lanes_cap, mesh))

    #: OOM halvings shrink the stage lane budget for the REST of the run
    #: (the device that OOM'd once at a shape will OOM again; re-probing
    #: it every stage would pay the fault each time).
    budget_scale = 1.0
    exhausted: list[int] = []  # packs that ran out of rungs unresolved
    #: Where the mesh rescue will not run and refutations are confirmed
    #: by worker sweeps, a pack that leaves the last rung undecided (its
    #: frontier outgrew the widest capacity) goes whole to the same
    #: exact sweep in the confirmation workers at once, concurrent with
    #: the rest of the ladder and the other confirmations, instead of to
    #: the serial in-process CPU fallback after the ladder.
    mesh_rescue = (dedup == "pallas" and mesh is not None
                   and mesh.devices.size > 1)
    sweep_undecided = cpu_fallback and confirm_refutations and not mesh_rescue
    n_swept = 0  # undecided packs handed to the worker sweeps
    #: Lowest-rung-first selection + rung-0 joiner admission could defer
    #: an escalated member forever under a steady arrival stream; a
    #: member skipped for more than _STARVE_SECONDS gets its rung served
    #: next (bounded wait, not strict priority).  Only continuous
    #: ladders need it — without admission the lowest rung drains
    #: monotonically.  k -> perf_counter() of the first skipped launch.
    starve: dict[int, float] = {}
    while pending or admission is not None:
        _poll_admission()
        if admission is not None:
            _poll_confirmations()
        # Members past the last rung leave the ladder (post-ladder
        # unknowns: the exact-confirm/CPU-fallback tail decides them).
        past = [k for k in pending if rungs[k] >= len(stages)]
        if past:
            pending = [k for k in pending if rungs[k] < len(stages)]
            if not sweep_undecided or (deadline is not None
                                       and deadline.expired()):
                exhausted.extend(past)
                past = []
        for k in past:
            # no failure barrier to bound it: the sweep runs whole
            i = idxs[k]
            pool, fut = _submit_confirmation(
                confirm_workers, model, list(histories[i]),
                confirm_max_configs, None,
            )
            confirm_futs[i] = (pool, fut, results[i], time.perf_counter(),
                               None, obs.capture())
            _pv(i, "ladder.exhausted", route="worker-sweep")
            n_swept += 1
        if not pending:
            if admission is not None and confirm_futs:
                # Linger while worker confirmations are in flight: keep
                # demuxing finished confirms early and keep ADMITTING —
                # a joiner arriving during the confirm tail enters rung
                # 0 of THIS ladder instead of seeding a narrow
                # follow-up batch (the tail was measured as a whole
                # second service cycle at ~0.4 occupancy).  Only LIVE
                # futures are worth lingering for: _poll_confirmations
                # demuxes clean successes only, so a dead entry (failed
                # submit left fut=None, or a future holding an
                # exception) would spin this loop forever — those
                # belong to the final drain's retry machinery below, as
                # does everything once the deadline expires.
                live = any(
                    e[1] is not None and not e[1].done()
                    for e in confirm_futs.values()
                )
                if live and (deadline is None or not deadline.expired()):
                    time.sleep(0.001)
                    continue
            break  # ladder drained and the hook (if any) had nothing
        si = min(rungs[k] for k in pending)
        if admission is not None and starve:
            waiting = [k for k in pending if k in starve]
            if waiting:
                k_worst = min(waiting, key=lambda k: starve[k])
                if time.perf_counter() - starve[k_worst] > _STARVE_SECONDS:
                    si = rungs[k_worst]
        group = [k for k in pending if rungs[k] == si]
        rest = [k for k in pending if rungs[k] != si]
        if admission is not None:
            for k in group:
                starve.pop(k, None)
            t_skip = time.perf_counter()
            for k in rest:
                starve.setdefault(k, t_skip)
        st_engine, batch_cap = stages[si]
        if deadline is not None and deadline.expired():
            # Deadline-bounded degradation: checkpoint FIRST (the saved
            # placeholders keep their resumable causes), then mark every
            # remaining pack unknown with an attributable cause plus a
            # pointer to the checkpoint.  The CPU fallback is skipped
            # for these — the budget is spent.
            deadline_tripped = True
            ck = _save_checkpoint(si)
            trip_checkpointed = ck is not None
            obs.event("fault.deadline", at="ladder-stage", stage=si,
                      unresolved=len(pending))
            obs.counter("fault.deadline.trip")
            note = f"; resumable checkpoint: {ck}" if ck else ""
            for k in pending:
                i = idxs[k]
                prev = results[i]
                _pv(i, "fault.deadline", at="ladder-stage", stage=si)
                results[i] = {
                    "valid?": "unknown",
                    "cause": (
                        "deadline-exceeded: check budget exhausted before "
                        f"ladder stage {si}{note}"
                    ),
                }
                if isinstance(prev, dict) and prev.get("kernel"):
                    results[i]["kernel"] = prev["kernel"]
                no_fallback.add(i)
            obs.gauge("ladder.unknowns_remaining", len(pending), final=True)
            pending = []
            break
        _reset_launch_acc()
        t_stage = time.perf_counter()
        stage_attrs = dict(
            stage=si, engine=st_engine, capacity=batch_cap,
            lanes=len(group), dedup=dedup,
        )
        if dedup == "pallas" and st_engine == "async" and group:
            # Fused-kernel rungs carry the kernel's tile/VMEM occupancy
            # on their ladder.stage rows (estimate at the rung's widest
            # pack shape — stage_occupancy is pure arithmetic), plus an
            # honest interpret flag so chip rows stay separable.  A
            # rung whose geometry statically routes AWAY from the
            # kernel is counted: silent fallback would read as "the
            # kernel ran" in exactly the stage rows built to decide
            # the chip-day flip.
            from jepsen_tpu.ops import wide_kernel as _wk

            _pP = max(packs[k]["P"] for k in group)
            _pG = max(packs[k]["G"] for k in group)
            _pW = (_pP + 31) // 32
            _occ = _wk.stage_occupancy(batch_cap, _pP, _pG,
                                       max_count=_pP + 1)
            # routed = the full gate (geometry AND the per-launch VMEM
            # working-set model) — a rung the budget spills off the
            # kernel must read as fallback in the stage rows, exactly
            # like a geometry miss
            _routed = _wk.fused_feasible(
                _occ["candidates"], batch_cap, _pP + 1, w=_pW, g=_pG)
            _n_mesh = int(mesh.devices.size) if mesh is not None else 1
            stage_attrs.update(
                pallas_routed=_routed, pallas_tile=_occ["tile"],
                pallas_vmem_bytes=_occ["vmem_bytes"],
                pallas_vmem_budget_bytes=_occ["vmem_budget_bytes"],
                pallas_interpret=_occ["interpret"],
                mesh_devices=_n_mesh,
            )
            if not _routed:
                obs.counter("dedup.pallas_fallback",
                            stage=si, capacity=batch_cap)
                if _n_mesh > 1:
                    # the mesh-spanning stage can still lift this rung:
                    # record whether its per-device VMEM model says so
                    _mocc = _wk.mesh_occupancy(
                        batch_cap, _pP, _pG, W=_pW,
                        max_count=_pP + 1, devices=_n_mesh)
                    stage_attrs.update(
                        pallas_mesh_feasible=_mocc["feasible"])
        # Measured-shape guard (round 5): the batched exact runner
        # faults the TPU worker on long-scan x wide-frontier shapes
        # (boundary table in wgl.exact_scan_safe).  Lanes past the
        # boundary take the chunked exact path — short chunk scans with
        # a carried frontier, same content-decided kills — instead of
        # joining the batched launch.  Unsafe-ness is monotone in
        # capacity, so such a lane is handled ONCE with the full
        # remaining exact ladder (chunked_analysis escalates only the
        # overflowing chunks) and never re-enters a later rung.
        if st_engine == "exact":
            safe = []
            exact_ladder = [c for e, c in stages[si:] if e == "exact"]
            # the guard sees the PADDED lane count of the stage's widest
            # launch, the one the kernel actually holds resident (the
            # fault grid is single-lane; vmap multiplies the live
            # buffers by the lane count)
            lanes_cap = _lanes_per_launch(_EXACT_LANE_BUDGET, batch_cap)
            n_lanes = padded_batch(min(lanes_cap, len(group)), mesh,
                                   batch_cap, _pinned_width(lanes_cap))
            for k in group:
                if wgl.exact_scan_safe(
                        wgl.pad_B(packs[k]["B"]), batch_cap, n_lanes):
                    safe.append(k)
                    continue
                i = idxs[k]
                _pv(i, "route.chunked-exact", stage=si, capacity=batch_cap)
                r = wgl.chunked_analysis(
                    model, histories[i], packs[k], exact_ladder,
                    rounds=int(rounds), fast=False, dedup_backend=dedup,
                    deadline=deadline, frontier_budget_mb=frontier_budget_mb,
                )
                _pv_merge(i, r.pop("provenance", None)
                          if isinstance(r, dict) else None)
                results[i] = r
                _notify(i)
            group = safe
            if not group:
                _emit_stage(t_stage, stage_attrs, unknowns_remaining=0)
                pending = rest
                continue
        # Bound total frontier rows per launch so wide-capacity stages
        # sub-batch instead of faulting the TPU worker (observed at
        # capacity*lanes ≳ 64k on the exact engine, whose sort and
        # domination buffers are ~10x the async kernel's per-lane
        # footprint, so the async and greedy stages get larger budgets;
        # re-measure the true thresholds on-chip).
        if st_engine == "exact":
            budget = _EXACT_LANE_BUDGET
        elif st_engine == "async":
            budget = _CARRY_LANE_BUDGET
        else:
            budget = _GREEDY_LANE_BUDGET
        # Carried-frontier fetch (round 5): resume snapshots leave the
        # device only for lanes that STAY pending, and only when a later
        # async rung exists to resume them — each lane's pre-loss
        # frontier then seeds the wider rung instead of re-running the
        # whole history from barrier 0.  The fetch happens per chunk,
        # IMMEDIATELY after that chunk's launch (the verdict arrays are
        # host-side by then), so at most one chunk's snapshot is ever
        # device-resident — the lanes budget's resident-row bound holds
        # across sub-batches.  The unconditional full-batch fetch this
        # replaces measured ~0.8 s of device-to-host round-trips on the bench
        # ladder (chip ablation, round 5).
        fetch_snaps = st_engine == "async" and any(
            e == "async" for e, _ in stages[si + 1:])
        lane_out: dict[int, tuple] = {}  # pack idx -> (valid, fat, lossy, peak)
        degraded: list[tuple[int, str]] = []  # (pack idx, cause)
        # A serial rung runs every launch at the whole group's shape and
        # tick budget: one compiled shape, and each lane's outcome what
        # one launch of the group would give it.
        group_shape = None
        if _serial(batch_cap):
            group_shape = _launch_shape(
                [packs[k] for k in group],
                [resumes.get(k) for k in group]
                if st_engine == "async" else None)

        def _launch_ft(part: list[int], pad_to: int | None = None,
                       retry: bool = False, spilled: bool = False) -> None:
            """Launch one sub-batch under the fault policy: transient
            errors retry with backoff inside faults.call_with_retry; an
            OOM first asks the registered device-memory spillers to free
            something (faults.try_oom_spill — runner-cache eviction on
            real accelerators) and retries the SAME launch once, then
            halves the sub-batch recursively (floor one lane — and the
            stage lane budget shrinks with it, so later chunks don't
            re-probe the fault); a part that still fails degrades ONLY
            its lanes, never the batch.  Spill-retry and halved
            sub-launches run with ``retry=True`` so their reduced sizes
            stay out of the watchdog's launch-time EWMA.  Successful
            parts land their verdicts in lane_out and fetch their
            pending lanes' resume snapshots immediately (at most one
            part's snapshot is ever device-resident, preserving the
            lane budget's resident-row bound)."""
            nonlocal budget_scale
            sub_res = ([resumes.get(k) for k in part]
                       if st_engine == "async" else None)
            ctx = dict(
                what=f"ladder.{st_engine}", stage=si, engine=st_engine,
                capacity=batch_cap, lanes=len(part),
            )
            try:
                out = faults.call_with_retry(
                    lambda: _launch(
                        st_engine, batch_cap, [packs[k] for k in part],
                        sub_res, pad_to, retry, group_shape,
                    ),
                    ctx,
                )
            except faults.LaunchFailure as lf:
                if lf.kind == "oom" and not spilled and faults.try_oom_spill(ctx):
                    # Spill rung of the OOM ladder: device memory was
                    # freed — retry the SAME shape once at full size
                    # before shrinking any work.
                    obs.counter(
                        "fault.launch.oom_spill_retry", stage=si,
                        engine=st_engine, capacity=batch_cap,
                        lanes=len(part),
                    )
                    for k in part:
                        _pv(idxs[k], "fault.oom-spill-retry", stage=si,
                            engine=st_engine, capacity=batch_cap)
                    _launch_ft(part, pad_to, retry=True, spilled=True)
                    return
                if lf.kind == "oom" and len(part) > 1:
                    mid = (len(part) + 1) // 2
                    budget_scale = max(budget_scale / 2, 1.0 / max(1, budget))
                    obs.counter(
                        "fault.launch.oom_halving", stage=si,
                        engine=st_engine, capacity=batch_cap,
                        lanes_from=len(part), lanes_to=mid,
                    )
                    for k in part:
                        _pv(idxs[k], "fault.oom-halving", stage=si,
                            engine=st_engine, capacity=batch_cap)
                    # Fault path: drop the fixed continuous-batching pad
                    # — replaying the halved part back up to the width
                    # that just OOM'd would re-probe the fault.
                    _launch_ft(part[:mid], retry=True, spilled=spilled)
                    _launch_ft(part[mid:], retry=True, spilled=spilled)
                    return
                cause = faults.describe(lf.cause)
                obs.counter(
                    "fault.launch.degraded", stage=si, engine=st_engine,
                    capacity=batch_cap, lanes=len(part), error=cause,
                )
                for k in part:
                    _pv(idxs[k], "fault.launch-degraded", stage=si,
                        engine=st_engine, capacity=batch_cap, error=cause)
                degraded.extend((k, cause) for k in part)
                return
            v, fat, lz, pk, snap = out
            for j, k in enumerate(part):
                lane_out[k] = (v[j], fat[j], lz[j], pk[j])
            if fetch_snaps and snap is not None:
                local = [
                    jl for jl in range(len(part))
                    if _stays_pending(v[jl], fat[jl], lz[jl])
                ]
                if local:
                    sel = jnp.asarray(np.asarray(local, np.int32))
                    bs, sst, sfo, sfc, sal = jax.device_get(
                        tuple(a[sel] for a in snap)
                    )
                    for t, jl in enumerate(local):
                        resumes[part[jl]] = (
                            int(bs[t]), sst[t], sfo[t], sfc[t], sal[t]
                        )
            del snap, out  # free the device snapshot before the next launch

        # Re-read the (possibly OOM-halved) scale for EVERY chunk: when
        # chunk 1 OOMs, chunks 2..n are sliced at the shrunken budget
        # instead of re-probing the fault at the original width.  The
        # continuous fixed pad is clamped to the chunk lane budget so
        # pinning the shape never exceeds the resident-row bound.
        s0 = 0
        launched_pad = 0
        while s0 < len(group):
            lanes_cap = _lanes_per_launch(budget * budget_scale, batch_cap)
            part = group[s0 : s0 + lanes_cap]
            pad_to = _pinned_width(lanes_cap)
            launched_pad += padded_batch(len(part), mesh, batch_cap, pad_to)
            _launch_ft(part, pad_to)
            s0 += lanes_cap
        if admission is not None and hasattr(admission, "on_rung"):
            # Post-stage occupancy report: the lanes that were live, the
            # padded lane-slots the kernel actually launched (the fixed
            # continuous width when pinned), and the rung's LAUNCH
            # seconds (compile + execute, from the launch accounting —
            # not the stage wall, which also counts host-side packing
            # and demux the device never saw) — so the caller can
            # weight occupancy by device time instead of counting a
            # 2 ms underfull greedy launch the same as a 300 ms
            # full-width beam rung.
            try:
                admission.on_rung(
                    stage=si, engine=st_engine, capacity=batch_cap,
                    lanes=len(group), padded=launched_pad,
                    seconds=launch_acc["compile_s"] + launch_acc["execute_s"],
                )
            except Exception:  # noqa: BLE001 — telemetry-only hook
                logger.exception("rung-admission on_rung failed")
        for k, cause in degraded:
            # a failed launch costs exactly its own lanes: each degrades
            # to unknown with the error named, and (when enabled) the
            # CPU fallback below still gets a chance to decide it
            results[idxs[k]] = {
                "valid?": "unknown",
                "cause": f"device launch failed: {cause}",
            }
        still = []
        n_true = n_refuted = n_submitted = 0
        peak_max = 0
        n_lossy = 0
        for k in group:
            if k not in lane_out:
                continue  # degraded this stage; its result is set above
            valid_k, fat_k, lossy_k, peak_k = lane_out[k]
            i = idxs[k]
            stats = {"frontier-peak": int(peak_k), "capacity": batch_cap, "lossy?": bool(lossy_k)}
            peak_max = max(peak_max, int(peak_k))
            n_lossy += bool(lossy_k)
            # the SAME predicate the snapshot fetch filtered on — a lane
            # fetched there is exactly a lane classified pending here
            pending_lane = _stays_pending(valid_k, fat_k, lossy_k)
            if not pending_lane and fat_k < 0:
                n_true += 1
                _pv(i, "ladder.stage", stage=si, engine=st_engine,
                    capacity=batch_cap, outcome="valid")
                results[i] = {"valid?": True, "kernel": stats}
                _notify(i)
            elif not pending_lane:
                n_refuted += 1
                op_pos = int(packs[k]["bar_opid"][int(fat_k)])
                op = histories[i][op_pos]
                res = {"valid?": False, "op": op, "kernel": stats}
                final = st_engine == "exact" or not confirm_refutations
                # "True" names the worker confirmation in the decision
                # path: evidence digests compare these strings
                _pv(i, "ladder.stage", stage=si, engine=st_engine,
                    capacity=batch_cap, outcome="refuted",
                    confirm="none" if final else "True")
                if final:
                    # content-decided kills (or the caller opted out):
                    # the refutation is final
                    results[i] = res
                    _notify(i)
                else:
                    # async refutation: hash-dedup could in
                    # principle have killed a distinct config, so the
                    # exact CPU sweep confirms it — in a worker
                    # process, concurrent with the remaining stages.
                    # op_pos (the positional id, same identity the sweep
                    # enumerates) bounds the sweep to the failure prefix.
                    pool, fut = _submit_confirmation(
                        confirm_workers, model, list(histories[i]),
                        confirm_max_configs, op_pos,
                    )
                    n_submitted += 1
                    confirm_futs[i] = (
                        pool, fut, res, time.perf_counter(), op_pos,
                        obs.capture(),
                    )
                    results[i] = res  # placeholder; resolved below
            else:
                still.append(k)
                _pv(i, "ladder.stage", stage=si, engine=st_engine,
                    capacity=batch_cap, outcome="pending",
                    lossy=bool(lossy_k))
                results[i] = {
                    "valid?": "unknown",
                    "cause": "frontier capacity or closure rounds exhausted",
                    "kernel": stats,
                }
        if n_submitted:
            obs.counter("confirm.submitted", n_submitted)
        for k in still:
            rungs[k] = si + 1
        pending = sorted(rest + still)
        _emit_stage(
            t_stage, stage_attrs, resolved=n_true, refuted=n_refuted,
            unknowns_remaining=len(still), peak_frontier=peak_max,
            lossy=n_lossy, degraded=len(degraded),
        )
        obs.gauge(
            "ladder.unknowns_remaining", len(still), stage=si, capacity=batch_cap
        )
        _save_checkpoint(
            min(rungs[k] for k in pending) if pending else si + 1
        )

    if exhausted and mesh_rescue:
        # Mesh rescue (round 12): before the ladder admits defeat, the
        # exhausted lanes get ONE run of the mesh-SPANNING fused stage —
        # the whole mesh as a single frontier at devices x the top rung
        # (the per-device VMEM model is what makes that capacity
        # feasible where a single chip spills).  True is a constructive
        # witness and lands outright; a refutation is hash-decided like
        # every fast-engine False and is confirmed by the bounded exact
        # sweep before it is reported; an unknown keeps the mesh-capacity
        # undecidability report as its cause.
        from jepsen_tpu.parallel import sharded as _sharded

        _n_mesh = int(mesh.devices.size)
        top_cap = max(batch_caps + exact_caps)
        rescue_cap = top_cap * _n_mesh
        t_rescue = time.perf_counter()
        still_exhausted = []
        for k in exhausted:
            i = idxs[k]
            if deadline is not None and deadline.expired():
                still_exhausted.append(k)
                continue
            _pv(i, "route.mesh-kernel", capacity=rescue_cap,
                mesh_devices=_n_mesh)
            r = _sharded.mesh_kernel_analysis(
                model, histories[i], mesh, capacity=(rescue_cap,),
                rounds=int(rounds),
            )
            if r["valid?"] is True:
                results[i] = r
                no_fallback.add(i)
                _pv(i, "mesh-kernel.resolved", outcome="valid")
                _notify(i)
                continue
            if r["valid?"] is False:
                if not confirm_refutations:
                    # unconfirmed fast-engine False: carries its honest
                    # provisional? flag, same contract as the ladder
                    results[i] = r
                    no_fallback.add(i)
                    _pv(i, "mesh-kernel.resolved",
                        outcome="refuted-provisional")
                    _notify(i)
                    continue
                fat = int(r.get("kernel", {}).get("failed-at", -1))
                op_pos = (int(packs[k]["bar_opid"][fat])
                          if fat >= 0 else None)
                cpu_res = wgl_cpu.sweep_analysis(
                    model, histories[i],
                    max_configs=confirm_max_configs,
                    stop_at_index=op_pos,
                )
                results[i] = _resolve_confirmation(r, cpu_res)
                decided = results[i].get("valid?") != "unknown"
                _pv(i, "mesh-kernel.resolved" if decided
                    else "mesh-kernel.unconfirmed",
                    outcome=_prov.verdict_str(results[i].get("valid?")))
                if decided:
                    no_fallback.add(i)
                    _notify(i)
                    continue
                still_exhausted.append(k)
                continue
            # unknown even at mesh capacity: the mesh-capacity
            # undecidability report becomes the attributable cause
            if r.get("cause"):
                results[i] = r
            _pv(i, "mesh-kernel.exhausted")
            still_exhausted.append(k)
        obs.span_event(
            "ladder.mesh_rescue", time.perf_counter() - t_rescue,
            capacity=rescue_cap, mesh_devices=_n_mesh,
            lanes=len(exhausted),
            resolved=len(exhausted) - len(still_exhausted),
        )
        exhausted = still_exhausted

    if exhausted:
        # The lanes the whole ladder failed to resolve: a final gauge
        # plus an attributable cause in each unknown result.
        obs.gauge("ladder.unknowns_remaining", len(exhausted), final=True)
        if exact_caps:
            note = (
                f"capacity ladder {tuple(batch_caps)} and exact escalation "
                f"{tuple(exact_caps)} exhausted"
            )
        else:
            note = (
                f"capacity ladder {tuple(batch_caps)} exhausted with no "
                "exact-escalation stages (exact_escalation=None means none "
                "since round 3)"
            )
        for k in exhausted:
            i = idxs[k]
            _pv(i, "ladder.exhausted")
            r = results[i]
            if r is not None and r.get("valid?") == "unknown" and r.get("cause"):
                r["cause"] = f"{r['cause']}; {note}"

    if cpu_fallback:
        t_fb = time.perf_counter()
        n_fb = 0
        for i, r in enumerate(results):
            if deadline is not None and deadline.expired():
                # The budget is spent: the remaining unknowns keep their
                # attributable causes instead of starting CPU sweeps the
                # deadline can no longer cover.
                if not deadline_tripped:
                    deadline_tripped = True
                    obs.counter("fault.deadline.trip")
                    obs.event("fault.deadline", at="cpu-fallback")
                break
            if (r is not None and r["valid?"] == "unknown"
                    and i not in confirm_futs and i not in early_confirmed
                    and i not in no_fallback):
                # The config-set sweep, not the DFS: DFS backtracking goes
                # exponential on exactly the histories that overflow the
                # kernel (info-heavy invalid ones); the sweep is the same
                # frontier algorithm the kernel runs and degrades linearly.
                n_fb += 1
                results[i] = wgl_cpu.sweep_analysis(model, histories[i])
                _pv(i, "cpu-fallback", engine="sweep",
                    outcome=_prov.verdict_str(results[i].get("valid?")))
                _notify(i)
        if n_fb:
            obs.span_event(
                "ladder.cpu-fallback", time.perf_counter() - t_fb, histories=n_fb
            )

    def _degrade_confirmation(i: int, dev_res: dict, e: BaseException) -> None:
        """A confirmation worker died (twice, after the bounded
        resubmit): degrade THIS history only, never the batch.  With
        cpu_fallback (and budget left) the sweep re-runs in-process —
        if the worker died because the sweep itself raises
        deterministically (model bug, malformed history), the re-run
        raises the SAME error and still degrades this history alone
        (advisor r4)."""
        _pv(i, "confirm.degraded", error=type(e).__name__)
        if cpu_fallback and not (deadline is not None and deadline.expired()):
            try:
                results[i] = wgl_cpu.sweep_analysis(
                    model, histories[i], max_configs=confirm_max_configs
                )
                return
            except Exception as e2:  # noqa: BLE001
                results[i] = {
                    "valid?": "unknown",
                    "cause": (
                        "device refutation; confirmation sweep raised: "
                        f"{e2!r}"
                    ),
                    "kernel": dev_res.get("kernel"),
                }
                return
        results[i] = {
            "valid?": "unknown",
            "cause": f"device refutation; confirmation worker failed: {e!r}",
            "kernel": dev_res.get("kernel"),
        }

    t_drain = time.perf_counter()
    for i, (pool, fut, dev_res, t_submit, op_pos, ctx) in confirm_futs.items():
        with obs.attach(ctx):
            # The re-attached submit-time context: every event this
            # resolution emits carries the originating trace, even
            # though the sweep itself ran in a worker process.
            resubmitted = False
            while True:
                try:
                    if fut is None:
                        raise BrokenProcessPool(
                            "no confirmation worker available")
                    timeout = None
                    if deadline is not None:
                        # leave a small grace so nearly-done sweeps land;
                        # a timeout degrades this history alone (the
                        # checkpoint kept its descriptor for a resume)
                        timeout = max(5.0, deadline.remaining())
                    cpu_res = fut.result(timeout=timeout)
                    break
                except FutureTimeout:
                    deadline_tripped = True
                    confirm_degraded.add(i)
                    obs.counter("fault.deadline.trip")
                    obs.event("fault.deadline", at="confirm-drain", history=i)
                    _pv(i, "fault.deadline", at="confirm-drain")
                    results[i] = {
                        "valid?": "unknown",
                        "cause": (
                            "device refutation; deadline-exceeded before the "
                            "confirmation sweep finished"
                        ),
                        "kernel": dev_res.get("kernel"),
                    }
                    cpu_res = None
                    break
                except BrokenProcessPool:
                    # Reset only the pool the failure came from, and only
                    # while it is still installed: a stale future's error
                    # must not shut down a healthy rebuilt pool that other
                    # histories' confirmations are running on.
                    if pool is not None and pool is _CONFIRM_POOL:
                        _reset_confirm_pool()
                    if not resubmitted:
                        # The in-flight task died WITH the pool: one bounded
                        # resubmit against the rebuilt pool before degrading
                        # (a broken pool is usually one bad worker, not a
                        # deterministic task failure).
                        resubmitted = True
                        obs.counter("fault.confirm.resubmit", history=i)
                        _pv(i, "confirm.resubmit")
                        pool, fut = _submit_confirmation(
                            confirm_workers, model, list(histories[i]),
                            confirm_max_configs, op_pos,
                        )
                        continue
                    cpu_res = _degrade_confirmation(
                        i, dev_res,
                        BrokenProcessPool("confirmation worker failed twice"),
                    )
                    break
                except Exception as e:  # noqa: BLE001 — a dead worker must
                    # not lose the other histories' verdicts; this one only
                    cpu_res = _degrade_confirmation(i, dev_res, e)
                    break
            if cpu_res is None:
                continue
            # Queue latency: submit-to-resolution — how much of the sweep
            # ran concurrently with the remaining ladder stages vs in the
            # drain.
            obs.gauge(
                "confirm.queue_latency_s",
                round(time.perf_counter() - t_submit, 6), history=i,
            )
            results[i] = _resolve_confirmation(dev_res, cpu_res)
            # mode stays "worker" whether the future was harvested early
            # or in this drain: harvest TIMING is scheduling noise, and
            # digest parity compares decision paths across runs (the
            # drain itself is on the ladder.confirm.drain span).
            _pv(i, "confirm.resolved", mode="worker",
                outcome=_prov.verdict_str(results[i].get("valid?")))
        _notify(i)
    if confirm_futs:
        obs.span_event(
            "ladder.confirm.drain", time.perf_counter() - t_drain,
            confirmations=len(confirm_futs), undecided=n_swept,
        )

    if packs and batch_caps and obs.active() is not None:
        # Per-round dedup timing for this run's first-rung candidate
        # shape, BOTH backends (one dedup.round span each): the sort-vs-
        # bucket comparison the kernel rounds themselves can't emit
        # (they run inside a jitted scan), surfaced in telemetry.json's
        # "dedup" table and tools/trace_summarize.py.  Telemetry-gated
        # AND once per shape per process: a couple ms, never a
        # recurring tax on long runs.
        pP = wgl._bucket(max(p["P"] for p in packs), list(P_BUCKETS))
        pG = wgl._bucket(max(p["G"] for p in packs), list(G_BUCKETS))
        shape = (batch_caps[0], pP, pG)
        if shape not in _PROBED_DEDUP_SHAPES:
            _PROBED_DEDUP_SHAPES.add(shape)
            t_probe = time.perf_counter()
            hashing.dedup_round_probe(batch_caps[0], pP, pG, (pP + 31) // 32)
            obs.span_event(
                "ladder.dedup-probe", time.perf_counter() - t_probe,
                capacity=batch_caps[0], active_backend=dedup,
            )
    if checkpoint_dir is not None and not trip_checkpointed:
        # Final checkpoint: "complete" unless a deadline trip left
        # resumable work (degraded confirmations keep their descriptors
        # so a resume can finish them; a complete checkpoint makes a
        # later resume idempotent — saved verdicts, no device work).
        # Skipped when a trip already wrote its resumable checkpoint —
        # overwriting it would destroy exactly the state a resume needs.
        confirm_futs = {
            i: t for i, t in confirm_futs.items() if i in confirm_degraded
        }
        _save_checkpoint(
            len(stages),
            complete=not deadline_tripped and not confirm_degraded,
        )
    out = [r if r is not None else {"valid?": "unknown"} for r in results]
    for i, r in enumerate(out):
        _prov.attach(r, prov_paths.get(i, []), engine=prov_engine,
                     config=prov_cfg)
    return out
