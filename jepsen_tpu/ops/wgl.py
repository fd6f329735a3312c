"""Frontier-parallel Wing–Gong–Lowe linearizability search on TPU.

This is the rebuild's Knossos replacement (BASELINE.json north star): the
configuration-set sweep of jepsen_tpu.checker.wgl_cpu.sweep_analysis,
vectorized.  Where the JVM checker walks configurations one at a time with
a DFS stack, this kernel advances the *entire frontier* of configurations
through each return barrier as fixed-shape tensor ops under one jit'd
lax.scan — breadth-parallelism instead of backtracking.

Data layout (all static shapes; F = frontier capacity, P = process slots,
G = crashed-op groups, W = ⌈P/32⌉ bitset lanes, B = barriers):

  frontier:  state[F] int32 · fok[F,W] uint32 (fired-open-op bitset by
             process slot) · fcr[F,G] int16 (fired count per crashed
             group; counts gated ≤ 32767 at pack time) · alive[F] bool
  barriers:  per-barrier op (f,v1,v2,slot), per-slot open-op table
             (mov_*[B,P]), per-group open counts (grp_open[B,G])

Per barrier: a bounded closure loop (lax.while_loop, ≤R rounds) expands
every config by every legal move — firing any open ok op (process move) or
one crashed op from any group (group move) — then dedups (hash-sorted,
content-confirmed) and compacts to capacity keeping fewest-fired configs
first (sort-based, jepsen_tpu.ops.hashing).  Then configs that fired the
returning op survive; the op's slot bit is cleared and the scan advances.

Soundness contract (SURVEY.md §7 hard-part #1: "never a wrong verdict"):
any transition applied is legal, so a surviving frontier is a constructive
witness — ``True`` is always sound, truncated or not.  ``False`` requires
that no capacity/round loss occurred anywhere (``lossy`` flag); on the
single-history path (chunked_analysis) kills are content-decided
(frontier_update / exact_prune), so its refutations are exact, while the
batched async engine dedups by 64-bit row hash and its refutations are
therefore CONFIRMED on the exact CPU sweep before being reported
(jepsen_tpu.parallel.batch_analysis overlaps the confirmation with the
remaining device stages, so it is sound and nearly free in wall clock).
Anything else answers ``"unknown"`` and the ``competition`` front-end
falls back to the CPU oracle.

The same structural optimizations as the CPU sweep apply: crashed-op
canonicalization into (f, value) groups, and fewest-fired-first compaction
(domination order) under truncation.
"""

from __future__ import annotations

import bisect
import functools
import json
import os
import time
import warnings
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from jepsen_tpu import faults
from jepsen_tpu import history as h
from jepsen_tpu import models as m
from jepsen_tpu import obs
from jepsen_tpu.checker import wgl_cpu
from jepsen_tpu.obs import provenance as _prov
from jepsen_tpu.models import tensor as tmodels
from jepsen_tpu.ops.hashing import (
    frontier_update,
    frontier_update_fast,
    resolve_dedup_backend,
)



I32 = jnp.int32
I16 = jnp.int16  #: fired-crashed counts ride int16 — halves the G-column
#: traffic that dominates pairwise prunes (counts are gated ≤ 32767 by pack)
U32 = jnp.uint32


class NotTensorizable(Exception):
    """History/model can't be packed for the kernel (exotic model, f, or
    value vocabulary); callers fall back to the CPU oracle."""


# ---------------------------------------------------------------------------
# Host-side packing
# ---------------------------------------------------------------------------


def _encode_value(value) -> tuple[int, int]:
    try:
        v1, v2 = h.encode_register_value(None, list(value) if isinstance(value, tuple) else value)
    except TypeError as e:
        raise NotTensorizable(str(e)) from None
    return v1, v2


def pack(model: m.Model, history: Sequence[dict]):
    """Pack a history into the kernel's barrier tables.

    Raises NotTensorizable when the model has no tensor step function or
    ops carry values the int32 columns can't hold.

    A stored ``ColumnHistory`` takes the COLUMN-NATIVE path (round 5,
    VERDICT item 7): the event/effective-op pass and the barrier tables
    are built straight from the SoA columns — the store→kernel chain
    materializes no per-op dicts at all (the ``.jepsen`` file's encoded
    (value1, value2) pairs ARE the kernel's value columns; knossos
    ``complete`` semantics applied by swapping in the completion's
    pair).  Falls back to the dict path when the model has a precheck
    (it consumes op dicts) or extras override client-op fields.
    """
    tm = tmodels.tensor_model_for(model)
    if tm is None:
        raise NotTensorizable(f"no tensor model for {getattr(model, 'name', model)!r}")
    if (
        isinstance(history, h.ColumnHistory)
        and tm.precheck is None
        and history.positional()
        and not any(
            {"value", "type", "process"} & e.keys()
            for i, e in history.extras.items()
            if history.cols["process"][i] != -1  # -1 = the nemesis sentinel
        )
    ):
        return _pack_columns(tm, model, history)
    history = h.materialize(history)
    events, eff_ops, crashed = wgl_cpu.prepare(model, history)
    if tm.precheck is not None:
        try:
            tm.precheck(model, eff_ops.values())
        except ValueError as e:
            raise NotTensorizable(str(e)) from None
    barriers, group_ops = wgl_cpu._barrier_snapshots(events, eff_ops, crashed)
    B = len(barriers)

    def fcode(op) -> int:
        f = op["f"]
        if f not in tm.f_codes:
            raise NotTensorizable(f"model {tm.name} has no f code for {f!r}")
        return tm.f_codes[f]

    # Process slots: one in-flight ok op per process at a time.
    slots: dict = {}
    for i in eff_ops:
        if i not in crashed:
            p = history[i]["process"]
            if p not in slots:
                slots[p] = len(slots)
    P = max(1, len(slots))
    W = (P + 31) // 32

    groups = sorted(group_ops, key=repr)
    gidx = {g: k for k, g in enumerate(groups)}
    G = max(1, len(groups))

    bar_f = np.zeros(B, np.int32)
    bar_v1 = np.zeros(B, np.int32)
    bar_v2 = np.zeros(B, np.int32)
    bar_slot = np.zeros(B, np.int32)
    bar_opid = np.zeros(B, np.int32)
    mov_f = np.zeros((B, P), np.int32)
    mov_v1 = np.zeros((B, P), np.int32)
    mov_v2 = np.zeros((B, P), np.int32)
    mov_open = np.zeros((B, P), bool)
    grp_open = np.zeros((B, G), np.int32)

    bar_quiet = np.zeros(B, bool)

    # Per-op (fcode, v1, v2) memo: an op stays open across many barriers
    # and was re-encoded at every one (measured 850k _encode_value calls
    # for a 100k-op history; one per effective op suffices).
    codes: dict[int, tuple[int, int, int]] = {}

    def op_codes(j: int) -> tuple[int, int, int]:
        t = codes.get(j)
        if t is None:
            oj = eff_ops[j]
            v1, v2 = _encode_value(oj.get("value"))
            t = codes[j] = (fcode(oj), v1, v2)
        return t

    for b, (_pos, i, open_ok, open_crashed) in enumerate(barriers):
        bar_quiet[b] = open_ok == (i,)
        bar_f[b], bar_v1[b], bar_v2[b] = op_codes(i)
        bar_slot[b] = slots[history[i]["process"]]
        bar_opid[b] = i
        for j in open_ok:
            s = slots[history[j]["process"]]
            mov_f[b, s], mov_v1[b, s], mov_v2[b, s] = op_codes(j)
            mov_open[b, s] = True
        for g, count in open_crashed:
            grp_open[b, gidx[g]] = count

    grp_f = np.zeros(G, np.int32)
    grp_v1 = np.zeros(G, np.int32)
    grp_v2 = np.zeros(G, np.int32)
    for g, k in gidx.items():
        grp_f[k] = fcode(group_ops[g])
        grp_v1[k], grp_v2[k] = _encode_value(group_ops[g].get("value"))

    return _finish_pack(
        tm, model, B, P, G, W, bar_quiet,
        (bar_f, bar_v1, bar_v2, bar_slot), bar_opid,
        (mov_f, mov_v1, mov_v2, mov_open),
        (grp_f, grp_v1, grp_v2), grp_open,
    )


def _finish_pack(tm, model, B, P, G, W, bar_quiet, bar, bar_opid, mov, grp, grp_open):
    """Shared tail of both pack paths: the int16 count gate, the slot
    one-hot layout, and the kernel-table contract (one copy — the dict
    and column paths must never drift)."""
    if B and grp_open.max(initial=0) > 32767:
        raise NotTensorizable("crashed-group open count exceeds int16 range")
    slot_lane = np.arange(P, dtype=np.int32) // 32
    slot_onehot = np.zeros((P, W), np.uint32)
    for p in range(P):
        slot_onehot[p, p // 32] = np.uint32(1) << np.uint32(p % 32)
    return {
        "B": B,
        "P": P,
        "G": G,
        "W": W,
        "init_state": np.int32(_encode_state(tm, model)),
        "step": tm.step,
        "bar_active": np.ones(B, bool),
        "bar_quiet": bar_quiet,
        "bar": bar,
        "bar_opid": bar_opid,
        "mov": mov,
        "grp": grp,
        "grp_open": grp_open,
        "slot_lane": slot_lane,
        "slot_onehot": slot_onehot,
    }


def _pack_columns(tm, model, ch):
    """Column-native pack: one pass over a ColumnHistory's SoA columns.

    Mirrors the dict path exactly (prepare → _barrier_snapshots → table
    fill, wgl_cpu.prepare semantics: fail ops dropped, crashed pure ops
    dropped, completion values become effective values) but the working
    values are the stored encoded ``(value1, value2)`` int pairs — no op
    dict is ever built.  Group keys are ``(f_code, v1, v2)`` triples;
    group ORDER is sorted on the triple (the dict path sorts on repr of
    the python values), which only permutes the grp columns — verdict-
    irrelevant, every reference to a group goes through its index."""
    cols, fs = ch.cols, ch.fs
    n = len(ch)
    # ColumnHistory._TYPE_NAMES order
    T_INVOKE, T_OK, T_FAIL, T_INFO = 0, 1, 2, 3
    typl = np.asarray(cols["type"]).tolist()
    procl = np.asarray(cols["process"]).tolist()
    fl = np.asarray(cols["f"]).tolist()
    v1l = np.asarray(cols["value1"]).tolist()
    v2l = np.asarray(cols["value2"]).tolist()
    fmap = [tm.f_codes.get(name) for name in fs]
    pure = wgl_cpu.PURE_FS.get(getattr(model, "name", None), set())
    pure_idx = {k for k, name in enumerate(fs) if name in pure}
    NILi = int(h.NIL)

    # pair matching (pair_index semantics, on plain ints)
    pair = [-1] * n
    open_by_p: dict = {}
    for i in range(n):
        if typl[i] == T_INVOKE:
            open_by_p[procl[i]] = i
        else:
            j = open_by_p.pop(procl[i], None)
            if j is not None:
                pair[j] = i
                pair[i] = j

    # effective ops + event order (wgl_cpu.prepare, columnar)
    order: list[tuple[int, int, int]] = []
    eff: dict[int, tuple[int, int, int]] = {}  # opid -> (code, v1, v2)
    crashed: set[int] = set()
    for i in range(n):
        # only -1 is the nemesis sentinel; other negative ints are
        # legitimate (if odd) client process ids the dict path includes
        if typl[i] != T_INVOKE or procl[i] == -1:
            continue
        j = pair[i]
        ctype = typl[j] if j != -1 else T_INFO
        if ctype == T_FAIL:
            continue
        fi = fl[i]
        if ctype == T_INFO and fi in pure_idx:
            continue
        code = fmap[fi]
        if code is None:
            raise NotTensorizable(f"model {tm.name} has no f code for {fs[fi]!r}")
        ev1, ev2 = v1l[i], v2l[i]
        if ctype == T_OK and not (v1l[j] == NILi and v2l[j] == NILi):
            ev1, ev2 = v1l[j], v2l[j]  # knossos complete: learn the value
        eff[i] = (code, ev1, ev2)
        order.append((i, wgl_cpu.CALL, i))
        if ctype == T_OK:
            order.append((j, wgl_cpu.RET, i))
        else:
            crashed.add(i)
    order.sort()

    # slots: one in-flight ok op per process
    slots: dict = {}
    for i in eff:
        if i not in crashed:
            p = procl[i]
            if p not in slots:
                slots[p] = len(slots)
    P = max(1, len(slots))
    W = (P + 31) // 32
    B = sum(1 for _pos, kind, _i in order if kind == wgl_cpu.RET)

    # group vocabulary over the whole history (deterministic triple sort)
    groups = sorted({eff[i] for i in crashed})
    gidx = {g: k for k, g in enumerate(groups)}
    G = max(1, len(groups))

    bar_f = np.zeros(B, np.int32)
    bar_v1 = np.zeros(B, np.int32)
    bar_v2 = np.zeros(B, np.int32)
    bar_slot = np.zeros(B, np.int32)
    bar_opid = np.zeros(B, np.int32)
    mov_f = np.zeros((B, P), np.int32)
    mov_v1 = np.zeros((B, P), np.int32)
    mov_v2 = np.zeros((B, P), np.int32)
    mov_open = np.zeros((B, P), bool)
    grp_open = np.zeros((B, G), np.int32)
    bar_quiet = np.zeros(B, bool)

    open_ok: list[int] = []
    open_crashed: dict[tuple, int] = {}
    b = 0
    for _pos, kind, i in order:
        if kind == wgl_cpu.CALL:
            if i in crashed:
                g = eff[i]
                open_crashed[g] = open_crashed.get(g, 0) + 1
            else:
                open_ok.append(i)
        else:
            bar_quiet[b] = open_ok == [i]
            bar_f[b], bar_v1[b], bar_v2[b] = eff[i]
            bar_slot[b] = slots[procl[i]]
            bar_opid[b] = i
            for jj in open_ok:
                s = slots[procl[jj]]
                mov_f[b, s], mov_v1[b, s], mov_v2[b, s] = eff[jj]
                mov_open[b, s] = True
            for g, count in open_crashed.items():
                grp_open[b, gidx[g]] = count
            b += 1
            k = bisect.bisect_left(open_ok, i)
            if k < len(open_ok) and open_ok[k] == i:
                del open_ok[k]

    grp_f = np.zeros(G, np.int32)
    grp_v1 = np.zeros(G, np.int32)
    grp_v2 = np.zeros(G, np.int32)
    for g, k in gidx.items():
        grp_f[k], grp_v1[k], grp_v2[k] = g

    return _finish_pack(
        tm, model, B, P, G, W, bar_quiet,
        (bar_f, bar_v1, bar_v2, bar_slot), bar_opid,
        (mov_f, mov_v1, mov_v2, mov_open),
        (grp_f, grp_v1, grp_v2), grp_open,
    )


def _encode_state(tm, model) -> int:
    try:
        return tm.encode_state(model)
    except ValueError as e:
        raise NotTensorizable(str(e)) from None


def _bucket(x: int, choices) -> int:
    for c in choices:
        if c >= x:
            return c
    return x


def pad_packed(packed: dict, B: int | None = None, P: int | None = None, G: int | None = None) -> dict:
    """Pad the packed tables to bucketed shapes so the jitted kernel is
    reused across histories instead of recompiling per (B, P, G) triple.
    Padding barriers are inactive (skipped); padding slots/groups are never
    open, so the kernel's behavior is unchanged.  Explicit targets override
    the buckets (used to align a batch of histories on common shapes)."""
    B0, P0, G0 = packed["B"], packed["P"], packed["G"]
    B = B if B is not None else pad_B(B0)
    P = P if P is not None else _bucket(P0, [8, 16, 32, 64, 128])
    G = G if G is not None else _bucket(G0, [4, 8, 16, 32, 64])
    assert B >= B0 and P >= P0 and G >= G0
    if (B, P, G) == (B0, P0, G0):
        return packed
    W = (P + 31) // 32
    bar_f, bar_v1, bar_v2, bar_slot = packed["bar"]
    mov_f, mov_v1, mov_v2, mov_open = packed["mov"]
    grp_f, grp_v1, grp_v2 = packed["grp"]

    def padB(a, fill=0):
        out = np.full((B,) + a.shape[1:], fill, a.dtype)
        out[:B0] = a
        return out

    def padBP(a):
        out = np.zeros((B, P), a.dtype)
        out[:B0, :P0] = a
        return out

    def padG(a):
        out = np.zeros(G, a.dtype)
        out[:G0] = a
        return out

    def padBG(a):
        out = np.zeros((B, G), a.dtype)
        out[:B0, :G0] = a
        return out

    slot_lane = np.arange(P, dtype=np.int32) // 32
    slot_onehot = np.zeros((P, W), np.uint32)
    for p in range(P):
        slot_onehot[p, p // 32] = np.uint32(1) << np.uint32(p % 32)
    out = dict(packed)
    out.update(
        B=B,
        P=P,
        G=G,
        W=W,
        bar_active=padB(packed["bar_active"], False),
        bar_quiet=padB(packed["bar_quiet"], False),
        bar=(padB(bar_f), padB(bar_v1), padB(bar_v2), padB(bar_slot)),
        mov=(padBP(mov_f), padBP(mov_v1), padBP(mov_v2), padBP(mov_open)),
        grp=(padG(grp_f), padG(grp_v1), padG(grp_v2)),
        grp_open=padBG(packed["grp_open"]),
        slot_lane=slot_lane,
        slot_onehot=slot_onehot,
    )
    return out


# ---------------------------------------------------------------------------
# Device kernel
# ---------------------------------------------------------------------------


def expand_candidates(
    step, eye_g, slot_lane, slot_mask, slot_onehot,
    state, fok, fcr, alive,
    xmov_f, xmov_v1, xmov_v2, xmov_open,
    grp_f, grp_v1, grp_v2, xgrp_open,
):
    """One closure round's candidate table: parents + every legal single
    move (shared by the single-device and frontier-sharded kernels).

    Process moves fire any open ok op not yet fired; group moves fire one
    crashed op from any open group.  A crashed fire that leaves the state
    unchanged yields a config dominated by its own parent (same state/fok,
    one more fired) — dropped at the source.

    Returns (cat_state, cat_fok, cat_fcr, cat_alive, cost) with
    F*(1+P+G) rows."""
    F, W = fok.shape
    P = xmov_f.shape[0]
    G = grp_f.shape[0]
    pstate2, plegal = step(state[:, None], xmov_f[None, :], xmov_v1[None, :], xmov_v2[None, :])
    already = (jnp.take(fok, slot_lane, axis=1) & slot_mask[None, :]) != 0
    plegal = plegal & alive[:, None] & xmov_open[None, :] & ~already
    pfok = (fok[:, None, :] | slot_onehot[None, :, :]).reshape(F * P, W)
    pfcr = jnp.repeat(fcr, P, axis=0)
    gstate2, glegal = step(state[:, None], grp_f[None, :], grp_v1[None, :], grp_v2[None, :])
    glegal = (
        glegal & alive[:, None] & (fcr < xgrp_open[None, :]) & (gstate2 != state[:, None])
    )
    gfok = jnp.repeat(fok, G, axis=0)
    gfcr = (fcr[:, None, :] + eye_g[None, :, :]).reshape(F * G, G)

    cat_state = jnp.concatenate([state, pstate2.reshape(-1), gstate2.reshape(-1)])
    cat_alive = jnp.concatenate([alive, plegal.reshape(-1), glegal.reshape(-1)])
    cat_fok = jnp.concatenate([fok, pfok, gfok], axis=0)
    cat_fcr = jnp.concatenate([fcr, pfcr, gfcr.astype(I16)], axis=0)
    cost = (
        jax.lax.population_count(cat_fok).sum(axis=1).astype(I32)
        + cat_fcr.sum(axis=1, dtype=I32)
    )
    return cat_state, cat_fok, cat_fcr, cat_alive, cost


def _scan_chunk_core(
    step,
    F: int,
    R: int,
    P: int,
    G: int,
    W: int,
    fast: bool,
    state0,
    fok0,
    fcr0,
    alive0,
    bar_active,
    bar_f,
    bar_v1,
    bar_v2,
    bar_slot,
    mov_f,
    mov_v1,
    mov_v2,
    mov_open,
    grp_f,
    grp_v1,
    grp_v2,
    grp_open,
    slot_lane,
    slot_onehot,
    dedup: str = "sort",
):
    """Scan a frontier over a chunk of barriers, starting from an explicit
    frontier and returning the final one.

    This is the composable unit behind both the whole-history runner
    (_run_core) and the chunked escalation path (chunked_analysis): because
    the frontier is carried in and out, a long history becomes a chain of
    small scan programs — no single XLA program ever holds tens of
    thousands of scan steps (the shape that faulted the TPU worker), and
    each chunk can re-run at a wider capacity on its own.

    Returns (state, fok, fcr, alive, failed_at, lossy, peak): failed_at is
    the chunk-local barrier index where the frontier died (-1 = never);
    lossy/peak cover this chunk only.
    """
    eye_g = jnp.eye(G, dtype=I16)
    slot_mask = slot_onehot.sum(axis=1)  # [P] uint32 bit mask within its lane

    def expand_round(val):
        state, fok, fcr, alive, r, changed, lossy, fp, xs = val
        (xbar_slot, xmov_f, xmov_v1, xmov_v2, xmov_open, xgrp_open) = xs
        cat_state, cat_fok, cat_fcr, cat_alive, cost = expand_candidates(
            step, eye_g, slot_lane, slot_mask, slot_onehot,
            state, fok, fcr, alive,
            xmov_f, xmov_v1, xmov_v2, xmov_open,
            grp_f, grp_v1, grp_v2, xgrp_open,
        )
        if fast:
            # Closure terminates on the no-growth signal: no expansion
            # survived dedup ⟹ fixpoint (modulo the hash-dedup caveat
            # covered by refutation confirmation).  frontier_update_fast
            # domination-prunes its own buffer, so its output is already
            # an antichain — no outer prune (advisor r3: the double prune
            # doubled the hot loop's prune cost for zero alive change).
            state2, fok2, fcr2, alive2, ovf, fp2, child = frontier_update_fast(
                cat_state, cat_fok, cat_fcr, cat_alive, cost, F, n_parents=F,
                max_count=xmov_f.shape[-1] + 1, dedup_backend=dedup,
            )
            changed2 = (alive2 & child).any()
        else:
            state2, fok2, fcr2, alive2, ovf, fp2 = frontier_update(
                cat_state, cat_fok, cat_fcr, cat_alive, cost, F,
                dedup_backend=dedup,
            )
            changed2 = ~(fp2 == fp).all()
        return (state2, fok2, fcr2, alive2, r + 1, changed2, lossy | ovf, fp2, xs)

    def round_cond(val):
        _s, _fo, _fc, _a, r, changed, _l, _fp, _xs = val
        return (r < R) & changed

    def barrier(carry, xs):
        state, fok, fcr, alive, failed_at, lossy, peak = carry
        b_idx, active, xbar_f, xbar_v1, xbar_v2, xbar_slot, xmov_f, xmov_v1, xmov_v2, xmov_open, xgrp_open = xs
        done = (failed_at >= 0) | ~active

        def process(_):
            xs_inner = (xbar_slot, xmov_f, xmov_v1, xmov_v2, xmov_open, xgrp_open)
            fp0 = jnp.full(3, jnp.uint32(0xFFFFFFFF))
            s2, fo2, fc2, a2, _r, changed, lossy2, _fp, _ = jax.lax.while_loop(
                round_cond,
                expand_round,
                (state, fok, fcr, alive, jnp.int32(0), jnp.bool_(True), lossy, fp0, xs_inner),
            )
            lossy3 = lossy2 | changed  # ran out of rounds before fixpoint
            # Filter: only configs that fired the returning op survive;
            # then retire its slot bit.
            lane = xbar_slot // 32
            bitmask = (U32(1) << (xbar_slot % 32).astype(U32))
            lane_vals = jnp.take(fo2, lane[None], axis=1)[:, 0]
            a3 = a2 & ((lane_vals & bitmask) != 0)
            clear = jnp.where(jnp.arange(W) == lane, bitmask, U32(0))
            fo3 = fo2 & ~clear[None, :]
            # fast path: the frontier is already an antichain (pruned
            # inside frontier_update_fast), and the return filter keeps
            # only rows holding the retiring bit, so the uniform clear
            # preserves both distinctness and domination order — no
            # per-barrier reap needed.
            dead = ~a3.any()
            failed2 = jnp.where(dead, b_idx, failed_at)
            peak2 = jnp.maximum(peak, a3.sum())
            return (s2, fo3, fc2, a3, failed2, lossy3, peak2)

        def skip(_):
            return (state, fok, fcr, alive, failed_at, lossy, peak)

        return jax.lax.cond(done, skip, process, None), None

    carry0 = (state0, fok0, fcr0, alive0, jnp.int32(-1), jnp.bool_(False),
              jnp.maximum(alive0.sum(), 1))
    xs = (
        jnp.arange(bar_f.shape[0], dtype=I32),
        bar_active,
        bar_f,
        bar_v1,
        bar_v2,
        bar_slot,
        mov_f,
        mov_v1,
        mov_v2,
        mov_open,
        grp_open,
    )
    (state, fok, fcr, alive, failed_at, lossy, peak), _ = jax.lax.scan(barrier, carry0, xs)
    return state, fok, fcr, alive, failed_at, lossy, peak


def _run_core(
    step,
    F: int,
    R: int,
    P: int,
    G: int,
    W: int,
    fast: bool,
    init_state,
    bar_active,
    bar_f,
    bar_v1,
    bar_v2,
    bar_slot,
    mov_f,
    mov_v1,
    mov_v2,
    mov_open,
    grp_f,
    grp_v1,
    grp_v2,
    grp_open,
    slot_lane,
    slot_onehot,
    dedup: str = "sort",
):
    """Scan the frontier over all barriers from the initial single-config
    frontier.  Returns (any_alive, failed_at, lossy, peak_frontier)."""
    state0 = jnp.full((F,), init_state, I32)
    fok0 = jnp.zeros((F, W), U32)
    fcr0 = jnp.zeros((F, G), I16)
    alive0 = jnp.zeros((F,), bool).at[0].set(True)
    _s, _fo, _fc, alive, failed_at, lossy, peak = _scan_chunk_core(
        step, F, R, P, G, W, fast,
        state0, fok0, fcr0, alive0,
        bar_active, bar_f, bar_v1, bar_v2, bar_slot,
        mov_f, mov_v1, mov_v2, mov_open,
        grp_f, grp_v1, grp_v2, grp_open,
        slot_lane, slot_onehot, dedup=dedup,
    )
    return alive.any(), failed_at, lossy, peak


_run = functools.partial(
    jax.jit, static_argnames=("step", "F", "R", "P", "G", "W", "fast", "dedup")
)(_run_core)

#: (step, F, R, P, G, W, fast, dedup) -> compiled-ahead chunk scan.
_CHUNK_RUNNERS: dict = {}


def chunk_runner(step, F: int, R: int, P: int, G: int, W: int, fast: bool,
                 dedup: str = "sort"):
    """``_scan_chunk_core`` specialised to its static shapes and compiled
    ahead of its launches (``faults.AheadOfTime``): called with
    (state0, fok0, fcr0, alive0, bar_active, ..., slot_onehot)."""
    key = (step, F, R, P, G, W, fast, dedup)
    _cache_counter(_CHUNK_RUNNERS, key, "chunk")
    if key not in _CHUNK_RUNNERS:
        core = functools.partial(_scan_chunk_core, step, F, R, P, G, W, fast,
                                 dedup=dedup)
        _CHUNK_RUNNERS[key] = faults.AheadOfTime(jax.jit(core))
    return _CHUNK_RUNNERS[key]

#: Bound on slices per chunk attempt: slice-width narrowing stops at
#: ceil(n_in / _MAX_SLICES), so one attempt never exceeds _MAX_SLICES
#: launches — wall clock stays bounded while headroom per entry row is
#: still capacity/width (64 slices at the host-bound frontier gives
#: 8x capacity headroom per row, ample for real closures).
_MAX_SLICES = 64

#: (step, F, R, P, G, W, dedup) -> jitted vmapped exact runner over a
#: leading batch axis.
_EXACT_RUNNERS: dict = {}


def exact_batched_runner(step, F: int, R: int, P: int, G: int, W: int,
                         dedup: str = "sort"):
    """jit(vmap(_run_core)) with the EXACT frontier update (sorted windowed
    (state, fok) compares + two-stage domination — kills are content
    compares, never hash-identity, under BOTH dedup backends).  Every
    straggler of an exact ladder rung runs in the same vmapped program,
    so the rung costs one launch instead of a Python loop of per-history
    exact escalations (~60% of bench wall clock in the round-2 profile)."""
    key = (step, F, R, P, G, W, dedup)
    _cache_counter(_EXACT_RUNNERS, key, "exact")
    if key not in _EXACT_RUNNERS:
        core = functools.partial(_run_core, step, F, R, P, G, W, False, dedup=dedup)
        axes = (0,) * 14 + (None, None)
        _EXACT_RUNNERS[key] = faults.AheadOfTime(jax.jit(jax.vmap(core, in_axes=axes)))
    return _EXACT_RUNNERS[key]


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def device_buffer_bytes() -> int | None:
    """Live device-buffer bytes on the primary device — the quantity the
    ladder's per-stage memory high-water marks sample (telemetry stage
    table ``device_bytes_peak``, live gauge ``device.buffer_bytes``).

    Prefers the backend allocator's ``bytes_in_use`` (TPU/GPU); falls
    back to summing live jax array footprints (the CPU backend exposes
    no allocator stats).  Returns None when neither is available —
    callers (all telemetry-gated) just skip the sample."""
    try:
        dev = jax.devices()[0]
        stats = dev.memory_stats()
        if stats and "bytes_in_use" in stats:
            return int(stats["bytes_in_use"])
    except Exception:  # noqa: BLE001 — stats are backend-optional
        pass
    try:
        return int(sum(int(a.nbytes) for a in jax.live_arrays()))
    except Exception:  # noqa: BLE001 — never fail a launch for a gauge
        return None


def _cache_counter(cache: dict, key, kind: str) -> None:
    """One compile-cache hit/miss counter per runner lookup: a fresh key
    means jit trace+compile is about to be paid (the compile_s column's
    event-level sibling; surfaced live via /metrics as
    ``wgl_runner_cache_hit/miss``)."""
    obs.counter(
        "wgl.runner_cache.hit" if key in cache else "wgl.runner_cache.miss",
        kind=kind,
    )


def evict_runner_caches() -> int:
    """Drop every cached jitted runner (exact / async / greedy / chunk):
    releasing the references lets the backend free the executables and
    their device buffers — the process-level spill lever the OOM policy
    pulls BEFORE halving work (``jepsen_tpu.faults.try_oom_spill``; the
    default spiller in ``parallel.batch`` calls this only on non-CPU
    backends, where allocator pressure is real).  The cost is
    recompiles later, never correctness.  Returns entries evicted."""
    caches = (_EXACT_RUNNERS, _ASYNC_RUNNERS, _GREEDY_RUNNERS, _CHUNK_RUNNERS)
    n = sum(len(c) for c in caches)
    for c in caches:
        c.clear()
    return n


#: Env var naming a MEASURED multi-lane fault-grid file
#: (tools/fault_sweep.py artifact): when set and valid, measured cells
#: replace the conservative lanes x capacity product-model inference in
#: exact_scan_safe — the round-6 caveat's fix.  Queries no measured
#: cell dominates still fall back to the product model (never less
#: conservative than the data actually covers).
EXACT_GRID_ENV = "JEPSEN_TPU_EXACT_GRID"

#: path -> (mtime_ns, size, cells-or-None) parse cache; re-reads only
#: when the file changes, so the hot routing path stays file-free.
_EXACT_GRID_CACHE: dict = {}
_EXACT_GRID_WARNED: set = set()


def validate_exact_grid(obj) -> list[dict]:
    """Validate a fault-grid artifact (tools/fault_sweep.py schema) and
    return its normalized cells.  Raises ValueError naming the first
    defect — the tool's --dry-run and the loader both gate on this, so
    a malformed grid can only ever fall back to the product model,
    never silently mis-route."""
    if not isinstance(obj, dict):
        raise ValueError("grid must be a JSON object")
    if obj.get("version") != 1:
        raise ValueError(f"unsupported grid version {obj.get('version')!r}")
    if obj.get("kind") != "exact-fault-grid":
        raise ValueError(f"grid kind must be 'exact-fault-grid', "
                         f"got {obj.get('kind')!r}")
    cells = obj.get("cells")
    if not isinstance(cells, list) or not cells:
        raise ValueError("grid needs a non-empty 'cells' list")
    out = []
    for i, c in enumerate(cells):
        if not isinstance(c, dict):
            raise ValueError(f"cell {i} is not an object")
        missing = {"lanes", "capacity", "barriers", "ok"} - c.keys()
        if missing:
            raise ValueError(f"cell {i} is missing {sorted(missing)}")
        if not isinstance(c["ok"], bool):
            raise ValueError(f"cell {i}: 'ok' must be a boolean")
        try:
            lanes, cap, bars = (
                int(c["lanes"]), int(c["capacity"]), int(c["barriers"])
            )
        except (TypeError, ValueError):
            raise ValueError(
                f"cell {i}: lanes/capacity/barriers must be integers"
            ) from None
        if min(lanes, cap, bars) < 1:
            raise ValueError(f"cell {i}: lanes/capacity/barriers must be >= 1")
        out.append({"lanes": lanes, "capacity": cap, "barriers": bars,
                    "ok": bool(c["ok"])})
    return out


def _exact_grid_cells(path: str) -> list[dict] | None:
    """Cached load of the measured grid; None (with a one-shot warning)
    on an unreadable/invalid file — conservative fallback, never a
    crash on the routing path."""
    try:
        st = os.stat(path)
        key = (st.st_mtime_ns, st.st_size)
    except OSError:
        key = None
    cached = _EXACT_GRID_CACHE.get(path)
    if cached is not None and cached[0] == key:
        return cached[1]
    cells = None
    try:
        with open(path, encoding="utf-8") as f:
            cells = validate_exact_grid(json.load(f))
    except (OSError, ValueError) as e:
        if path not in _EXACT_GRID_WARNED:
            _EXACT_GRID_WARNED.add(path)
            warnings.warn(
                f"ignoring {EXACT_GRID_ENV}={path!r} ({e}); the "
                "conservative product model stays in effect",
                stacklevel=3,
            )
        obs.counter("wgl.exact_grid.invalid")
    _EXACT_GRID_CACHE[path] = (key, cells)
    return cells


def _exact_grid_decide(cells: list[dict], B: int, capacity: int,
                       lanes: int) -> bool | None:
    """Decide a (B, capacity, lanes) query against measured cells.
    Fault danger is monotone in every axis (longer scans, wider
    frontiers, more resident lanes), so: a FAULT at a componentwise-
    dominated shape proves the query faults; an OK at a componentwise-
    dominating shape proves it is safe.  Contradictory data resolves
    conservatively (fault wins); an uncovered query returns None and
    the product model decides."""
    for c in cells:
        if (not c["ok"] and c["lanes"] <= lanes
                and c["capacity"] <= capacity and c["barriers"] <= B):
            return False
    for c in cells:
        if (c["ok"] and c["lanes"] >= lanes
                and c["capacity"] >= capacity and c["barriers"] >= B):
            return True
    return None


def exact_scan_safe(B: int, capacity: int, lanes: int = 1) -> bool:
    """Measured fault boundary of the batched exact runner (the round-4
    "cap >= 1024 faults the TPU worker" cliff, isolated by
    tools/repro_exact_fault.py on the v5e chip, round 5):

    | cap \\ barriers | 2048 | 4096 | 8192 |
    |---|---|---|---|
    | 512  | ok | ok | FAULT |
    | 1024 | ok | FAULT | FAULT |
    | 2048 | ok | FAULT | FAULT |

    The crash ("TPU worker process crashed or restarted ... kernel
    fault") needs BOTH a long barrier scan and a wide frontier: every
    B <= 2048 cell is fine (including cap 2048 — 4M rows), while the
    same 4M rows at B = 4096 faults.  The grid was measured on
    SINGLE-lane launches; under vmap the live sort/domination buffers
    multiply by the lane count, so callers pass the launch's PADDED
    lane count and the effective width ``lanes * capacity`` is tested.
    NOTE the lanes x capacity product model is an INFERENCE from the
    single-lane grid, not a measurement — no multi-lane fault point has
    been observed to confirm it (round-5 advisor).  It is conservative
    by construction, and the cost of that conservatism is routing, not
    correctness: multi-lane launches that would in fact be safe are
    sent to the chunked/async paths and pay only time (see PERF.md
    "Honest limits").  Callers must route shapes where this returns False to
    the async engine (which executes them — see PERF.md) or to
    chunked_analysis (whose chunk scans keep B <= the chunk size, far
    below the cliff).

    MEASURED-GRID OVERRIDE (round 11, the round-6 caveat's fix): when
    ``JEPSEN_TPU_EXACT_GRID`` names a ``tools/fault_sweep.py``
    artifact, its measured multi-lane cells decide first — a query
    dominated by a measured fault is unsafe, a query dominated BY a
    measured pass is safe (fault wins on contradiction) — and only
    queries the grid doesn't cover fall back to the inferred product
    model below.  A measured grid thus wins back exactly the mid-size
    batched-exact launches the inference conservatively re-routes,
    with zero new inference."""
    grid_path = os.environ.get(EXACT_GRID_ENV)
    if grid_path:
        cells = _exact_grid_cells(grid_path)
        if cells:
            verdict = _exact_grid_decide(cells, B, capacity, max(1, lanes))
            if verdict is not None:
                return verdict
    rows = capacity * max(1, lanes) * B
    if B >= 8192:  # faulted at EVERY measured cap; untested below 512
        return False
    if B >= 4096 and rows >= (4 << 20):
        return False
    if rows >= (8 << 20):  # untested headroom beyond the measured grid
        return False
    return True


def pad_B(B: int) -> int:
    """The barrier-table padding the batched launch sites apply (power
    of two, floor 64).  exact_scan_safe callers must check the PADDED
    shape — the one actually launched — so this lives next to it."""
    return 1 << max(6, (B - 1).bit_length())


def _chunk_bounds(quiet, B0: int, target: int) -> list[tuple[int, int]]:
    """Split [0, B0) into chunks of ≤ target barriers, preferring to cut
    just after the LATEST quiescent barrier in the back half of each window
    (a barrier whose only open ok op is the returning one): the carried
    frontier there has every fok bitset empty, so it collapses to the
    (state, crashed-count) antichain — the smallest summary the search ever
    holds (P-compositionality: the segments compose exactly through that
    summary)."""
    bounds = []
    lo = 0
    while lo < B0:
        hi_max = min(lo + target, B0)
        hi = hi_max
        if hi_max < B0:
            for b in range(hi_max - 1, lo + target // 2 - 1, -1):
                if quiet[b]:
                    hi = b + 1
                    break
        bounds.append((lo, hi))
        lo = hi
    return bounds


def chunked_analysis(
    model: m.Model,
    history: Sequence[dict],
    packed: dict,
    capacities: Sequence[int],
    rounds: int = 8,
    chunk_barriers: int = 512,
    fast: bool = False,
    dedup_backend: str | None = None,
    deadline=None,
    spill: bool | None = None,
    frontier_budget_mb: float | None = None,
    spill_factor: float = 4.0,
    spill_launches: int | None = None,
    factor_groups: bool | None = None,
    checkpoint_dir=None,
    resume: bool = False,
) -> dict:
    """Decide linearizability as a chain of chunk scans with a carried
    frontier (history decomposition — VERDICT round-2 item #2), under a
    BOUNDED device-memory contract (round 8): an overflowing frontier
    SPILLS to host instead of dying.

    Where the whole-history ladder re-ran ALL barriers at the next
    capacity whenever the frontier overflowed ANYWHERE, here only the
    overflowing chunk re-runs (from its exact input frontier) at the wider
    capacity; chunks the frontier sails through stay at the cheap
    capacity.  The capacity position adapts: it climbs on overflow and
    steps back down when a chunk's peak leaves 4x headroom.

    BOUNDED MEMORY (``spill``; default: engaged iff a bounded-memory
    knob — ``frontier_budget_mb`` or ``spill_launches`` — is set, since
    the recovery levers multiply launches on exactly the histories that
    are already slow): the frontier-set sweep is
    linear in the frontier — scanning a chunk from A ∪ B equals the
    union of scanning from A and from B — so a carried frontier that
    exceeds the rung capacity streams through the SAME compiled chunk
    kernel in slices of ≤ capacity rows, the overflow waiting in a
    host ring (``ops.spill.HostRing``; device→host copies start
    asynchronously, overlapping the next device-bound slice), and the
    slice survivors recombine by exact LSH-bucketed union
    (``ops.spill.merge_frontiers``).  Rows are never silently dropped;
    refutation requires EVERY slice to die.  ``frontier_budget_mb``
    (argument > JEPSEN_TPU_FRONTIER_BUDGET_MB env) caps the device
    frontier working set: ladder rungs that don't fit the budget are
    skipped and slicing absorbs the difference.  When a chunk still
    overflows at the highest usable rung, the chunk BISECTS — the
    frontier is re-checked (and spilled) at the midpoint — down to
    single-barrier chunks; only a single barrier's closure overflowing
    the budget is genuine exhaustion.  The lossy/escalation ladder thus
    engages only once spill is exhausted, and a final ``unknown`` then
    carries a machine-readable undecidability report
    (``ops.spill.undecidability_report``: peak frontier growth rate,
    spill volume, budget at exhaustion) in ``"undecidability"`` and a
    json rendering in ``cause`` — never a bare unknown (the report is
    NOT gated on spill: memory-exhausted unknowns carry it in the
    legacy truncation mode too).  ``spill=False`` forces the
    pre-round-8 truncate-and-latch-lossy behavior; ``spill=True``
    forces recovery on without a budget.

    ``factor_groups`` (None = rides the spill opt-in; True forces)
    first factors the packed problem over trace-independent crashed-op
    groups (``ops.spill.factor_packed``): each independent group is a
    factor whose check is closed-form, so it is removed and G shrinks
    structurally — the verdict provably equals the monolithic one.

    Soundness: ``True`` needs only a surviving frontier (any surviving
    config is a constructive witness, truncated or not).  ``False`` is
    reported only when no loss occurred in ANY chunk up to the death —
    once loss happens, a dead frontier answers "unknown".  The
    ``verified-barriers`` stat counts barriers passed with zero loss —
    the measured "verified ops" number for histories whose tail
    exhausts (BASELINE config 5).

    ``fast`` runs chunks on the hash-dedup engine (~10x cheaper per
    lane): ``True`` stays sound, but a ``False`` is PROVISIONAL (kills
    are hash-decided, collision ~1e-13) and is marked ``provisional?``
    for the caller to confirm, the way batch_analysis confirms
    fast-engine refutations.

    ``dedup_backend`` selects the per-round dedup backend for every
    chunk scan (None → env/default via resolve_dedup_backend).

    ``deadline`` (seconds or faults.Deadline) bounds wall clock at CHUNK
    boundaries: on expiry the run degrades to an attributable
    ``"unknown"`` instead of scanning past the budget.  Every chunk
    launch runs under the transient-retry policy
    (jepsen_tpu.faults.call_with_retry); a launch that still fails (or
    OOMs — there is no sub-batch to halve on the single-history path)
    degrades this history alone with the error named in ``cause``.

    ``checkpoint_dir`` persists the scan cursor and the carried —
    possibly host-spilled, so unbounded-row — frontier after every
    accepted chunk (``store.checkpoint.save_chunked``); ``resume=True``
    reloads it (fingerprint + config must match, else the run starts
    fresh with a warning — resuming against changed inputs could only
    produce wrong verdicts) and re-enters the chain at the saved
    barrier: a kill -9 mid-spill then a resume reproduces uninterrupted
    verdicts (chaos-gated in tools/chaos_check.py --spill).
    """
    from jepsen_tpu.ops import spill as spill_mod

    dedup = resolve_dedup_backend(dedup_backend)
    deadline = faults.Deadline.coerce(deadline)
    B0 = packed["B"]
    quiet = packed["bar_quiet"]
    budget_mb = spill_mod.resolve_budget_mb(frontier_budget_mb)
    #: Spill recovery is OPT-IN through the bounded-memory knobs: with
    #: no budget configured the scan keeps its pre-round-8 cost profile
    #: (truncate-and-latch-lossy — the escalation ladder alone), because
    #: the recovery levers multiply launches on exactly the histories
    #: that are slow already, and this path rides every escalation /
    #: confirmation fallback in the tier-1 suite.  Honest exhaustion
    #: reports are NOT gated — every memory-exhausted unknown carries
    #: one either way.  Resolved ONCE here; the factorization default,
    #: the checkpoint config, and the scan loop all read this value.
    spill_on = (
        bool(spill) if spill is not None
        else (budget_mb is not None or spill_launches is not None)
    )
    #: Factorization rides the same opt-in (None = auto): the
    #: reachable-state tabulation is cheap but nonzero per call, and the
    #: structural win matters exactly where memory pressure does.
    #: ``factor_groups=True`` forces it on.
    if factor_groups is None:
        factor_groups = spill_on
    factors = 0
    if factor_groups:
        packed, factors = spill_mod.factor_packed(packed)
    packed = pad_packed(packed, B=B0)  # bucket P/G; keep B for slicing
    P, G, W = packed["P"], packed["G"], packed["W"]
    caps = [int(c) for c in capacities]
    b_rows = spill_mod.budget_rows(budget_mb, W, G, P)

    # Decision-path trajectory (obs.provenance): a bounded trail of the
    # escalations, spill levers, and fault events this scan actually
    # took, attached to every return so the caller's evidence bundle
    # records HOW the verdict was produced.
    traj: list[dict] = []
    _prov_engine = {
        "engine": "chunked-fast" if fast else "chunked-exact",
        "dedup_backend": dedup, "spill": spill_on,
    }
    _prov_cfg = {
        "capacity": caps, "rounds": int(rounds),
        "chunk_barriers": int(chunk_barriers), "fast": bool(fast),
        "frontier_budget_mb": budget_mb,
        "spill_launches": spill_launches, "factor_groups": bool(factor_groups),
    }

    def _pv(event: str, **attrs) -> None:
        if len(traj) < _prov.MAX_PATH:
            traj.append({"event": event, **attrs})

    def _finish(res: dict) -> dict:
        _prov.attach(res, traj, engine=_prov_engine, config=_prov_cfg)
        return res

    def _usable(i: int) -> bool:
        """Rung i fits the device budget (rung 0 always runs — the
        documented floor: some capacity is needed to make progress)."""
        return i == 0 or b_rows is None or caps[i] <= b_rows

    # Host-side frontier bound: the union frontier is exact, which means
    # it can grow with the TRUE configuration count — exponential on
    # adversarial histories.  ``spill_factor`` × the widest usable rung
    # bounds the host rows (and with them the per-chunk launch count);
    # crossing it is memory exhaustion like any other: honest truncation,
    # lossy latch, undecidability report with reason "host-budget".
    top_usable = max(c for i, c in enumerate(caps) if _usable(i))
    host_rows_max = max(int(spill_factor * top_usable), top_usable)

    bar_f, bar_v1, bar_v2, bar_slot = packed["bar"]
    mov_f, mov_v1, mov_v2, mov_open = packed["mov"]
    slot_lane = jnp.asarray(packed["slot_lane"])
    slot_onehot = jnp.asarray(packed["slot_onehot"])
    grp_args = tuple(jnp.asarray(a) for a in packed["grp"])

    f_state = np.array([packed["init_state"]], np.int32)
    f_fok = np.zeros((1, W), np.uint32)
    f_fcr = np.zeros((1, G), np.int16)
    idx = 0
    lossy_any = False
    peak_g = 1
    verified = 0
    launches = 0
    start_barrier = 0
    resume_spill_spent = 0
    ring = spill_mod.HostRing(W, G)
    exhaust_rep: dict | None = None
    t0 = time.perf_counter()

    # ------------------------------------------------------------------
    # Chunk checkpoint / resume (store.checkpoint chunked schema).
    # ------------------------------------------------------------------
    ck_cfg = None
    _ckpt = None
    if checkpoint_dir is not None or resume:
        from jepsen_tpu.store import checkpoint as _ckpt_mod

        _ckpt = _ckpt_mod
        ck_cfg = {
            "fingerprint": _ckpt.fingerprint([history]),
            "capacity": caps, "rounds": int(rounds),
            "chunk_barriers": int(chunk_barriers), "fast": bool(fast),
            "dedup": dedup, "budget_mb": budget_mb,
            "spill_factor": float(spill_factor),
            "spill_launches": spill_launches,
            "factor_groups": bool(factor_groups), "spill": spill_on,
        }
    if (resume and checkpoint_dir is not None and _ckpt is not None
            and _ckpt.chunked_exists(checkpoint_dir)):
        saved = None
        try:
            saved = _ckpt.load_chunked(checkpoint_dir)
        except _ckpt.CheckpointError as e:
            import logging

            logging.getLogger(__name__).warning(
                "unreadable chunk checkpoint in %s (%s); running fresh",
                checkpoint_dir, e)
            obs.counter("fault.checkpoint.mismatch", reason="unreadable")
        if saved is not None and saved["config"] != ck_cfg:
            import logging

            # Quarantine the stale pair aside (same contract as the
            # ladder checkpoint): a later resume with matching inputs
            # must not pick mismatched state back up.
            quarantined = _ckpt.quarantine_chunked(
                checkpoint_dir, reason="stale-fingerprint")
            logging.getLogger(__name__).warning(
                "chunk checkpoint in %s was written for different inputs "
                "or config; running fresh (stale files quarantined: %s)",
                checkpoint_dir, quarantined)
            obs.counter("fault.checkpoint.quarantined",
                        reason="fingerprint", files=quarantined)
            obs.counter("fault.checkpoint.mismatch", reason="fingerprint")
            saved = None
        if saved is not None:
            if saved["result"] is not None:
                # Idempotent finished-run resume: return the certified
                # result verbatim.  Its provenance already records the
                # decision path that PRODUCED the verdict; tagging the
                # no-op restore onto it would make the resumed result
                # (and its evidence digest) differ from the original.
                obs.span_event(
                    "fault.checkpoint.load", 0.0,
                    barrier=int(saved["barrier"]), chunked=True,
                    complete=True,
                )
                return saved["result"]
            st, fo, fc = saved["frontier"]
            f_state = np.asarray(st, np.int32)
            f_fok = np.asarray(fo, np.uint32)
            f_fcr = np.asarray(fc, np.int16)
            start_barrier = saved["barrier"]
            idx = min(saved["cap_idx"], len(caps) - 1)
            lossy_any = saved["lossy"]
            verified = saved["verified"]
            launches = saved["launches"]
            resume_spill_spent = saved.get("spill_spent", 0)
            obs.span_event(
                "fault.checkpoint.load", 0.0, barrier=start_barrier,
                rows=int(f_state.shape[0]), chunked=True,
            )
            _pv("checkpoint.restored", barrier=start_barrier)

    def _save_ck(barrier: int, result: dict | None = None) -> str | None:
        """Persist the chunk cursor + carried (spilled) frontier; a save
        failure is logged and never fails the analysis."""
        if checkpoint_dir is None or _ckpt is None:
            return None
        try:
            p = _ckpt.save_chunked(
                checkpoint_dir, config=ck_cfg, barrier=barrier, cap_idx=idx,
                frontier=(f_state, f_fok, f_fcr), lossy=lossy_any,
                verified=verified, launches=launches,
                spill_rows=ring.rows_total, spill_bytes=ring.bytes_total,
                spill_spent=spill_spent, result=result,
            )
            return str(p)
        except Exception:  # noqa: BLE001 — recovery aid, not verdict input
            import logging

            logging.getLogger(__name__).warning(
                "couldn't write chunk checkpoint to %s", checkpoint_dir,
                exc_info=True)
            obs.counter("fault.checkpoint.error")
            return None

    def _offset_bounds(start: int) -> list[tuple[int, int]]:
        if start >= B0:
            return []
        rel = _chunk_bounds(quiet[start:], B0 - start, int(chunk_barriers))
        return [(start + a, start + b) for a, b in rel]

    spans = _offset_bounds(start_barrier)
    n_spans0 = len(spans)
    #: the WHOLE history's span count: the default spill budget must be
    #: identical for a resumed and an uninterrupted run (spill_spent is
    #: restored from the checkpoint; a budget recomputed from only the
    #: REMAINING spans would shrink on resume and could flip verdicts)
    n_spans_full = (
        n_spans0 if start_barrier == 0 else len(_offset_bounds(0))
    )
    #: Spill WORK budget: extra launches the spill levers (multi-slice
    #: attempts, chunk bisection, slice narrowing) may spend beyond the
    #: one-launch-per-chunk baseline.  The exact union frontier can be
    #: exponential, so unbounded recovery would trade an unknown for an
    #: unbounded wall clock; when the budget is spent the scan falls
    #: back to the pre-spill truncate-and-latch-lossy behavior and the
    #: final report says so (reason "spill-budget").  The DEFAULT is
    #: deliberately small — a couple of recovery attempts per chunk —
    #: because it rides every escalation/confirmation path in the tier-1
    #: suite; callers with real memory-pressure workloads (the bench
    #: batch offenders) pass ``spill_launches`` explicitly and pair it
    #: with a deadline.  Restored from the chunk checkpoint on resume:
    #: a resumed run must not get FRESH budget, or its verdicts could
    #: diverge from the uninterrupted run's.
    spill_budget = (
        int(spill_launches) if spill_launches is not None
        else 2 * max(1, n_spans_full) + 8
    )
    spill_spent = resume_spill_spent

    def _stats(capacity: int) -> dict:
        s = {
            "frontier-peak": peak_g, "capacity": capacity,
            "lossy?": lossy_any, "chunks": n_spans0, "launches": launches,
            "spill-rows": ring.rows_total, "spill-bytes": ring.bytes_total,
        }
        if factors:
            s["factors"] = factors
        return s

    def _emit(valid, stats: dict) -> None:
        """One telemetry span per chunked run: the frontier-sweep stats the
        beam-search literature instruments (occupancy, loss, escalations)."""
        obs.span_event(
            "wgl.chunked", time.perf_counter() - t0, valid=valid,
            chunks=stats.get("chunks"), launches=stats.get("launches"),
            peak_frontier=stats.get("frontier-peak"),
            capacity=stats.get("capacity"), lossy=stats.get("lossy?"),
            verified_barriers=stats.get("verified-barriers"), dedup=dedup,
            spill_rows=stats.get("spill-rows"),
            spill_bytes=stats.get("spill-bytes"),
            factors=stats.get("factors"),
        )

    def _attach_report(res: dict) -> dict:
        """An unknown that exhausted fixed memory carries the
        machine-readable report — the OOM ladder never lies.  Only the
        GENERIC capacity cause is rewritten to the report rendering: a
        deadline or launch-failure unknown keeps its own cause (and its
        resumable-checkpoint pointer) with the report attached
        alongside under ``"undecidability"``."""
        if exhaust_rep is not None and res.get("valid?") == "unknown":
            res["undecidability"] = exhaust_rep
            if res.get("cause") in (
                    None, "frontier capacity or closure rounds exhausted"):
                res["cause"] = spill_mod.undecidable_cause(exhaust_rep)
        return res

    # Every chunked verdict records at least the scan itself — a clean
    # no-escalation pass must still be distinguishable, in the evidence
    # bundle, from a run that never reached the chunked engine.
    _pv("wgl.chunk.scan", barriers=int(B0), chunks=len(spans),
        capacity=caps[idx], start_barrier=start_barrier)
    si = 0
    while si < len(spans):
        lo, hi = spans[si]
        if deadline is not None and deadline.expired():
            obs.counter("fault.deadline.trip")
            obs.event("fault.deadline", at="wgl-chunk", barrier=lo)
            _pv("fault.deadline", at="wgl-chunk", barrier=lo)
            ck = _save_ck(lo)
            note = f"; resumable checkpoint: {ck}" if ck else ""
            stats = _stats(caps[idx])
            stats["verified-barriers"] = verified
            _emit("unknown", stats)
            return _finish(_attach_report({
                "valid?": "unknown",
                "cause": (
                    "deadline-exceeded: check budget exhausted at barrier "
                    f"{lo}/{B0}{note}"
                ),
                "kernel": stats,
            }))
        Bc = 1 << max(5, (hi - lo - 1).bit_length())

        def padc(a, fill=0):
            out = np.full((Bc,) + a.shape[1:], fill, a.dtype)
            out[: hi - lo] = a[lo:hi]
            return out

        c_args = tuple(
            jnp.asarray(padc(a, fill))
            for a, fill in [
                (packed["bar_active"], False),
                (bar_f, 0), (bar_v1, 0), (bar_v2, 0), (bar_slot, 0),
                (mov_f, 0), (mov_v1, 0), (mov_v2, 0), (mov_open, False),
            ]
        )
        c_grp_open = jnp.asarray(padc(packed["grp_open"]))
        n_in = f_state.shape[0]
        # Climb the entry rung while the carried frontier doesn't fit and
        # a LARGER, budget-usable rung exists (one launch beats many
        # slices; the budget ceiling routes the rest through spill).
        while (idx + 1 < len(caps) and caps[idx] < n_in
               and caps[idx + 1] > caps[idx] and _usable(idx + 1)):
            idx += 1
        trunc = False
        width = None  # entry rows per slice; F on entry, halves on retry
        while True:
            F = caps[idx]
            if width is None:
                width = F
            if spill_on:
                # Slices of ≤ width entry rows, each scanned at the FULL
                # kernel capacity F: width < F buys closure headroom
                # (F/width growth per entry row) — the in-chunk lever
                # between bisection and exhaustion.
                cuts = list(range(0, n_in, width)) or [0]
                if len(cuts) > 1:
                    obs.counter("wgl.chunk.slices", len(cuts))
                    spill_spent += len(cuts) - 1
            else:
                cuts = [0]
            slice_outs = []
            for a in cuts:
                b = min(a + width, n_in) if spill_on else min(a + F, n_in)
                k = max(1, b - a)  # the initial 1-row frontier case
                # k < n_in with a single cut: the carried frontier
                # overflows this capacity (spill=False compatibility
                # path) and live configs are dropped — loss, IF this
                # attempt is the one kept (discarded attempts re-slice
                # the untruncated frontier, so they lose nothing).
                st0 = np.zeros(F, np.int32)
                fo0 = np.zeros((F, W), np.uint32)
                fc0 = np.zeros((F, G), np.int16)
                al0 = np.zeros(F, bool)
                st0[:k] = f_state[a:a + k]
                fo0[:k] = f_fok[a:a + k]
                fc0[:k] = f_fcr[a:a + k]
                al0[: b - a] = True
                try:
                    out = faults.call_with_retry(
                        lambda: chunk_runner(
                            packed["step"], F, int(rounds), P, G, W, fast,
                            dedup,
                        )(
                            jnp.asarray(st0), jnp.asarray(fo0),
                            jnp.asarray(fc0), jnp.asarray(al0), *c_args,
                            *grp_args, c_grp_open, slot_lane, slot_onehot,
                        ),
                        dict(what="wgl.chunk",
                             engine="fast" if fast else "exact",
                             capacity=F, lanes=1),
                    )
                except faults.LaunchFailure as lf:
                    ring.discard()
                    cause = faults.describe(lf.cause)
                    obs.counter("fault.launch.degraded", what="wgl.chunk",
                                capacity=F, lanes=1, error=cause)
                    _pv("fault.launch-degraded", capacity=F, error=cause)
                    stats = _stats(F)
                    stats["verified-barriers"] = verified
                    _emit("unknown", stats)
                    return _finish(_attach_report({
                        "valid?": "unknown",
                        "cause": f"device launch failed: {cause}",
                        "kernel": stats,
                    }))
                launches += 1
                slice_outs.append(out)
            trunc = not spill_on and n_in > F
            # Materialize the per-slice verdict scalars (blocks until
            # that slice's scan finishes; later slices keep computing on
            # the device stream behind it).
            sliced = []
            any_lossy = trunc
            peak_total = 0
            for s, fo, fc, al, failed_at, lossy, peak in slice_outs:
                failed_at, lossy, peak = int(failed_at), bool(lossy), int(peak)
                any_lossy |= lossy
                peak_total += peak
                sliced.append((s, fo, fc, al, failed_at))
            peak_g = max(peak_g, peak_total)
            nxt = idx + 1
            if (any_lossy and nxt < len(caps) and caps[nxt] > caps[idx]
                    and _usable(nxt)):
                obs.counter("wgl.chunk.escalations")
                _pv("chunk.escalation", barrier=lo, to_capacity=caps[nxt])
                ring.discard()
                idx = nxt  # re-run THIS chunk wider, from the same frontier
                width = None
                continue
            width_floor = max(1, (n_in + _MAX_SLICES - 1) // _MAX_SLICES)
            if (any_lossy and spill_on and spill_spent < spill_budget
                    and (hi - lo) == 1 and width > width_floor):
                # Single-barrier floor, still overflowing: narrow the
                # slices (same kernel capacity, fewer entry rows each)
                # before declaring exhaustion — down to the _MAX_SLICES
                # launch bound, where only a near-single config's
                # closure overflowing the budget rung remains, which is
                # undecidable under this memory.
                obs.counter("wgl.chunk.slice_narrowing")
                _pv("chunk.slice-narrowing", barrier=lo)
                ring.discard()
                spill_spent += 1
                width = max(width_floor, width // 2)
                continue
            break
        if (any_lossy and spill_on and spill_spent < spill_budget
                and (hi - lo) > 1):
            # Spill harder before going lossy: bisect the chunk so the
            # frontier is re-checked — and its overflow host-spilled —
            # at the midpoint (preferring a quiet cut, like the original
            # chunking).  Floor: a single barrier.
            ring.discard()
            rel = _chunk_bounds(quiet[lo:hi], hi - lo,
                                max(1, (hi - lo + 1) // 2))
            spans[si:si + 1] = [(lo + a, lo + b) for a, b in rel]
            obs.counter("wgl.chunk.bisections")
            _pv("chunk.bisection", barrier=lo)
            spill_spent += 1
            continue
        if spill_on and spill_spent >= spill_budget:
            # Spill work budget exhausted: the rest of the scan runs in
            # the pre-spill truncation mode; the report names the bound
            # that bit.
            spill_on = False
            _pv("spill.budget-exhausted", barrier=lo)
            if exhaust_rep is None:
                exhaust_rep = spill_mod.undecidability_report(
                    capacity=caps[idx], frontier_rows=n_in,
                    peak_frontier=peak_total, barrier=lo, barriers_total=B0,
                    budget_mb=budget_mb, budget_rows=b_rows,
                    spill_rows=ring.rows_total, spill_bytes=ring.bytes_total,
                    factor_count=factors,
                    device_buffer_bytes=device_buffer_bytes(),
                    reason="spill-budget",
                )
        if any_lossy and exhaust_rep is None:
            # Memory exhaustion: the accepted attempt lost rows — with
            # spill engaged that means a single barrier's closure
            # overflowed the highest budget-usable rung with nothing
            # left to split; in the legacy mode it is plain capacity
            # truncation.  Record the evidence; the scan continues
            # truncated (a surviving frontier still proves True), and
            # any final unknown carries this report.
            # the kernel reports the POST-filter peak; a lossy round by
            # definition overflowed the capacity, so the true closure
            # peak is at least capacity + 1 (the growth-rate evidence)
            exhaust_rep = spill_mod.undecidability_report(
                capacity=caps[idx], frontier_rows=n_in,
                peak_frontier=max(peak_total, caps[idx] + 1),
                barrier=lo, barriers_total=B0,
                budget_mb=budget_mb, budget_rows=b_rows,
                spill_rows=ring.rows_total, spill_bytes=ring.bytes_total,
                factor_count=factors,
                device_buffer_bytes=device_buffer_bytes(),
            )
        lossy_any |= any_lossy
        if trunc:
            obs.counter("wgl.frontier.truncations")
        if obs.observing():
            # Chunk-boundary device-memory sample: the chunked path is
            # the long-history workhorse, and its carried frontier is
            # exactly where resident bytes creep (telemetry-gated — the
            # allocator/live-array walk isn't free).
            db = device_buffer_bytes()
            if db is not None:
                obs.gauge("device.buffer_bytes", db, at="wgl-chunk",
                          barrier=lo)
        # --------------------------------------------------------------
        # Recombine: union the slice survivors.  A single slice fetches
        # directly (its output is already an antichain); multiple slices
        # stream through the host ring — device→host copies started at
        # push, exact LSH-bucketed dedup/domination at the merge.
        # --------------------------------------------------------------
        all_failed = all(f >= 0 for (_s, _fo, _fc, _al, f) in sliced)
        if all_failed:
            gb = lo + max(f for (_s, _fo, _fc, _al, f) in sliced)
            op_pos = int(packed["bar_opid"][gb])
            op = history[op_pos]
            stats = _stats(caps[idx])
            stats["bar-opid"] = op_pos  # positional id for stop_at_index
            stats["verified-barriers"] = verified
            # barriers the frontier survived carry a constructive witness
            # (prefix-True), loss or not — death at gb means gb barriers
            # were witnessed
            stats["witnessed-barriers"] = gb
            if lossy_any:
                _pv("chunk.lossy-death", barrier=gb)
                _emit("unknown", stats)
                return _finish(_attach_report({
                    "valid?": "unknown",
                    "cause": "frontier capacity or closure rounds exhausted",
                    "op": op,
                    "kernel": stats,
                }))
            _pv("chunk.refuted", barrier=gb,
                provisional=bool(fast))
            res = {"valid?": False, "op": op, "kernel": stats}
            if fast:
                res["provisional?"] = True  # hash-decided kills
            _emit(False, stats)
            return _finish(res)
        if not lossy_any:
            verified = hi
        if len(sliced) == 1:
            s, fo, fc, al, _f = sliced[0]
            al_h = np.asarray(al)
            sel = np.flatnonzero(al_h)
            f_state = np.asarray(s)[sel]
            f_fok = np.asarray(fo)[sel]
            f_fcr = np.asarray(fc)[sel]
        else:
            for s, fo, fc, al, f in sliced:
                if f < 0:  # dead slices contribute no rows
                    ring.push(s, fo, fc, al)
            popped = ring.pop_all()
            f_state, f_fok, f_fcr, _mstats = spill_mod.merge_frontiers(
                [popped] if popped is not None else [])
        rows = int(f_state.shape[0])
        if rows > host_rows_max:
            # Host budget exceeded: exact union tracking would now cost
            # more memory/launches than the configured bound — truncate
            # (candidate order: the most-speculative rows drop first),
            # latch loss, and record the evidence.  True stays sound.
            if exhaust_rep is None:
                exhaust_rep = spill_mod.undecidability_report(
                    capacity=caps[idx], frontier_rows=rows,
                    peak_frontier=peak_g, barrier=hi, barriers_total=B0,
                    budget_mb=budget_mb, budget_rows=b_rows,
                    spill_rows=ring.rows_total, spill_bytes=ring.bytes_total,
                    factor_count=factors,
                    device_buffer_bytes=device_buffer_bytes(),
                    reason="host-budget",
                )
            obs.counter("wgl.frontier.truncations")
            _pv("frontier.truncated", reason="host-budget", barrier=hi)
            f_state = f_state[:host_rows_max]
            f_fok = f_fok[:host_rows_max]
            f_fcr = f_fcr[:host_rows_max]
            lossy_any = True
            rows = host_rows_max
        if (idx > 0 and peak_total * 4 <= caps[idx - 1]
                and rows <= caps[idx - 1]):
            idx -= 1
        _save_ck(hi)
        si += 1
    stats = _stats(caps[idx])
    stats["verified-barriers"] = verified
    stats["witnessed-barriers"] = B0  # the survivor IS the whole-history witness
    _emit(True, stats)
    result = _finish({"valid?": True, "kernel": stats})
    _save_ck(B0, result=result)
    return result


def scan_barrier_range(
    packed: dict,
    frontier: tuple,
    lo: int,
    hi: int,
    *,
    capacities: Sequence[int],
    rounds: int = 8,
    chunk_barriers: int = 512,
    cap_idx: int = 0,
    lossy: bool = False,
    fast: bool = False,
    dedup_backend: str | None = None,
    spill: bool = False,
    on_event=None,
) -> dict:
    """Advance a carried frontier through barriers ``[lo, hi)`` of an
    already-padded pack — chunked_analysis's scan loop factored out so
    an INCREMENTAL caller (checker.streaming's per-epoch advance) can
    extend a running scan range by range instead of owning the whole
    history up front.

    ``packed`` must be ``pad_packed`` output with ``B`` kept at the true
    barrier count (the chunked-path convention) so ``lo``/``hi`` index
    real barriers; ``frontier`` is the carried ``(state, fok, fcr)``
    host arrays in the pack's padded ``(W, G)`` shapes.  Chunk cuts, the
    ``Bc`` padding rule, the capacity-escalation ladder, the dedup
    backend resolution, and the launch retry policy are all
    chunked_analysis's own — an epoch advance compiles no kernel
    geometry the post-hoc chunked path wouldn't.

    Returns a dict::

        frontier        surviving (state, fok, fcr), alive rows compacted
        failed_barrier  GLOBAL barrier index the frontier died at
                        (None = survived to ``hi``)
        cap_idx, lossy  adapted ladder position / latched loss flag —
                        thread them back into the next call
        launches, peak  accounting deltas for the caller's stats
        error           launch-failure cause string (scan aborted; the
                        caller degrades this range to unknown) or None

    Soundness is chunked_analysis's: death with ``lossy`` False refutes
    at ``failed_barrier`` exactly (content-decided kills when ``fast``
    is False); once any loss has latched, a death only means "unknown".
    ``spill`` slices an overflowing ENTRY frontier through the same
    kernel in ≤capacity-row slices and merges the survivors exactly
    (scan linearity — refutation then requires EVERY slice to die);
    without it overflow truncates and latches ``lossy``.

    ``on_event`` (optional callable ``(event, **attrs)``) receives the
    escalation/truncation events the chunked path would log to its
    decision-path trajectory, so the caller can record them under its
    own provenance prefix.
    """
    from jepsen_tpu.ops import spill as spill_mod

    dedup = resolve_dedup_backend(dedup_backend)
    caps = [int(c) for c in capacities]
    P, G, W = packed["P"], packed["G"], packed["W"]
    quiet = packed["bar_quiet"]
    bar_f, bar_v1, bar_v2, bar_slot = packed["bar"]
    mov_f, mov_v1, mov_v2, mov_open = packed["mov"]
    slot_lane = jnp.asarray(packed["slot_lane"])
    slot_onehot = jnp.asarray(packed["slot_onehot"])
    grp_args = tuple(jnp.asarray(a) for a in packed["grp"])

    f_state = np.asarray(frontier[0], np.int32)
    f_fok = np.asarray(frontier[1], np.uint32)
    f_fcr = np.asarray(frontier[2], np.int16)
    idx = min(max(int(cap_idx), 0), len(caps) - 1)
    lossy_any = bool(lossy)
    launches = 0
    peak_g = 0

    def _ev(event: str, **attrs) -> None:
        if on_event is not None:
            on_event(event, **attrs)

    def _out(failed=None, error=None):
        return {
            "frontier": (f_state, f_fok, f_fcr),
            "failed_barrier": failed, "cap_idx": idx, "lossy": lossy_any,
            "launches": launches, "peak": peak_g, "error": error,
        }

    if hi <= lo:
        return _out()
    spans = [
        (lo + a, lo + b)
        for a, b in _chunk_bounds(quiet[lo:hi], hi - lo, int(chunk_barriers))
    ]
    for clo, chi in spans:
        Bc = 1 << max(5, (chi - clo - 1).bit_length())

        def padc(a, fill=0):
            out = np.full((Bc,) + a.shape[1:], fill, a.dtype)
            out[: chi - clo] = a[clo:chi]
            return out

        c_args = tuple(
            jnp.asarray(padc(a, fill))
            for a, fill in [
                (packed["bar_active"], False),
                (bar_f, 0), (bar_v1, 0), (bar_v2, 0), (bar_slot, 0),
                (mov_f, 0), (mov_v1, 0), (mov_v2, 0), (mov_open, False),
            ]
        )
        c_grp_open = jnp.asarray(padc(packed["grp_open"]))
        n_in = f_state.shape[0]
        while (idx + 1 < len(caps) and caps[idx] < n_in
               and caps[idx + 1] > caps[idx]):
            idx += 1
        while True:
            F = caps[idx]
            cuts = list(range(0, n_in, F)) if spill and n_in > F else [0]
            slice_outs = []
            for a in cuts:
                b = min(a + F, n_in)
                k = max(1, b - a)  # the initial 1-row frontier case
                st0 = np.zeros(F, np.int32)
                fo0 = np.zeros((F, W), np.uint32)
                fc0 = np.zeros((F, G), np.int16)
                al0 = np.zeros(F, bool)
                st0[:k] = f_state[a:a + k]
                fo0[:k] = f_fok[a:a + k]
                fc0[:k] = f_fcr[a:a + k]
                al0[: b - a] = True
                try:
                    o = faults.call_with_retry(
                        lambda: chunk_runner(
                            packed["step"], F, int(rounds), P, G, W, fast,
                            dedup,
                        )(
                            jnp.asarray(st0), jnp.asarray(fo0),
                            jnp.asarray(fc0), jnp.asarray(al0), *c_args,
                            *grp_args, c_grp_open, slot_lane, slot_onehot,
                        ),
                        dict(what="wgl.chunk",
                             engine="fast" if fast else "exact",
                             capacity=F, lanes=1),
                    )
                except faults.LaunchFailure as lf:
                    cause = faults.describe(lf.cause)
                    obs.counter("fault.launch.degraded", what="wgl.chunk",
                                capacity=F, lanes=1, error=cause)
                    _ev("launch-degraded", capacity=F, error=cause)
                    return _out(error=cause)
                launches += 1
                slice_outs.append(o)
            trunc = not spill and n_in > F
            sliced = []
            any_lossy = trunc
            peak_total = 0
            for s, fo, fc, al, failed_at, sl, peak in slice_outs:
                failed_at, sl, peak = int(failed_at), bool(sl), int(peak)
                any_lossy |= sl
                peak_total += peak
                sliced.append((s, fo, fc, al, failed_at))
            peak_g = max(peak_g, peak_total)
            nxt = idx + 1
            if any_lossy and nxt < len(caps) and caps[nxt] > caps[idx]:
                obs.counter("wgl.chunk.escalations")
                _ev("escalation", barrier=clo, to_capacity=caps[nxt])
                idx = nxt  # re-run THIS chunk wider, from the same frontier
                continue
            break
        lossy_any |= any_lossy
        if trunc:
            obs.counter("wgl.frontier.truncations")
            _ev("truncated", barrier=clo)
        all_failed = all(f >= 0 for (_s, _fo, _fc, _al, f) in sliced)
        if all_failed:
            gb = clo + max(f for (_s, _fo, _fc, _al, f) in sliced)
            return _out(failed=gb)
        if len(sliced) == 1:
            s, fo, fc, al, _f = sliced[0]
            sel = np.flatnonzero(np.asarray(al))
            f_state = np.asarray(s)[sel]
            f_fok = np.asarray(fo)[sel]
            f_fcr = np.asarray(fc)[sel]
        else:
            ring = spill_mod.HostRing(W, G)
            for s, fo, fc, al, f in sliced:
                if f < 0:  # dead slices contribute no rows
                    ring.push(s, fo, fc, al)
            popped = ring.pop_all()
            f_state, f_fok, f_fcr, _mstats = spill_mod.merge_frontiers(
                [popped] if popped is not None else [])
        rows = int(f_state.shape[0])
        if (idx > 0 and peak_total * 4 <= caps[idx - 1]
                and rows <= caps[idx - 1]):
            idx -= 1
    return _out()


def analysis(
    model: m.Model,
    history: Sequence[dict],
    capacity: int | Sequence[int] = (128, 1024, 4096),
    rounds: int = 8,
    max_groups: int = 64,
    max_procs: int = 128,
    chunk_barriers: int = 512,
    fast: bool = False,
    dedup_backend: str | None = None,
    deadline=None,
    spill: bool | None = None,
    frontier_budget_mb: float | None = None,
    spill_factor: float = 4.0,
    spill_launches: int | None = None,
    factor_groups: bool | None = None,
    checkpoint_dir=None,
    resume: bool = False,
) -> dict:
    """Decide linearizability on the accelerator.

    Knossos-shaped result: ``{"valid?": True|False|"unknown", ...}`` plus
    kernel stats under ``"kernel"``.  True is always exact; False is exact
    unless the frontier overflowed (then "unknown").

    ``capacity`` may be a sequence: the per-chunk escalation ladder.  The
    history is scanned as a chain of ≤ ``chunk_barriers``-barrier chunk
    programs with the frontier carried between them (chunked_analysis):
    easy stretches stay on the small, fast frontier; branch-heavy chunks
    re-run at the next capacity — knossos-style competition, but against
    frontier sizes instead of algorithms, and at chunk granularity
    instead of whole-history granularity.
    """
    try:
        packed = pack(model, history)
    except NotTensorizable as e:
        return {"valid?": "unknown", "cause": f"not tensorizable: {e}"}
    if packed["B"] == 0:
        return {"valid?": True, "configs": [{"model": model}]}
    if packed["G"] > max_groups:
        return {"valid?": "unknown", "cause": f"{packed['G']} crashed-op groups exceeds {max_groups}"}
    if packed["P"] > max_procs:
        return {"valid?": "unknown", "cause": f"{packed['P']} process slots exceeds {max_procs}"}
    capacities = [capacity] if isinstance(capacity, int) else list(capacity)
    return chunked_analysis(
        model, history, packed, capacities, rounds, chunk_barriers, fast=fast,
        dedup_backend=dedup_backend, deadline=deadline, spill=spill,
        frontier_budget_mb=frontier_budget_mb, spill_factor=spill_factor,
        spill_launches=spill_launches, factor_groups=factor_groups,
        checkpoint_dir=checkpoint_dir, resume=resume,
    )


# ---------------------------------------------------------------------------
# Async-tick kernel: configs carry their own barrier index
# ---------------------------------------------------------------------------


def async_ticks(B: int, capacity: int | None = None) -> int:
    """Tick budget for the lane-async kernel.  Exceeding it flags lossy
    and escalates, so the cost of a low guess is a wasted stage, never a
    wrong verdict.

    Wide stages (capacity ≥ 1024) get ~2 closure rounds per barrier plus
    slack — the deep-closure work happens there (measured: the final 7
    ladder resolutions need the full budget; 4B+128 resolves nothing
    more).  Narrow stages get 1.5 rounds per barrier: their lanes either
    converge fast or escalate anyway, and the vmapped while_loop runs
    until the SLOWEST lane finishes, so budget-burning lossy lanes
    dictate the stage wall clock (measured ~8% off the full ladder at
    equal verdicts)."""
    if capacity is not None and capacity < 1024:
        return (3 * B) // 2 + 32
    return 2 * B + 64


def _run_core_async(
    step,
    F: int,
    T: int,
    B: int,
    P: int,
    G: int,
    W: int,
    bptr0,
    state0,
    fok0,
    fcr0,
    alive0,
    n_active,
    bar_f,
    bar_v1,
    bar_v2,
    bar_slot,
    mov_f,
    mov_v1,
    mov_v2,
    mov_open,
    grp_f,
    grp_v1,
    grp_v2,
    grp_open,
    slot_lane,
    slot_onehot,
    dedup: str = "sort",
):
    """Lane-asynchronous barrier stepping.

    The barrier-scan kernel (_run_core) closes each barrier to fixpoint
    inside a while_loop — under vmap every lane pays the MAX closure
    depth of any lane at every barrier (Σ_b max_lanes r_b).  Here the
    whole search is ONE scan of ``T`` uniform ticks: each tick runs one
    closure round at the lane's own current barrier; when the round
    reaches the closure fixpoint (no expansion survives content-
    confirmed dedup — an exact no-growth signal), the barrier's return
    filter applies and the lane's barrier pointer advances.  A barrier
    whose frontier is already closed advances in ONE tick.  Lanes drift
    apart freely, so the cost is max_lanes(Σ_b r_b) — each lane's own
    total closure depth.

    Semantics (and the soundness contract) are exactly _run_core's:
    same move algebra, same per-barrier filter, True only via a
    surviving frontier, False only when no loss occurred, tick-budget
    exhaustion or overflow → lossy → "unknown".

    CARRIED-FRONTIER RESUME (round 5): the search starts from an explicit
    (bptr0, frontier) instead of (0, single-config) — the escalation
    ladder resumes each straggler at its failure point instead of
    re-running the whole history wider.  The kernel returns, besides the
    verdict, a RESUME SNAPSHOT: the frontier as it stood at tick entry of
    the FIRST overflowing tick (exact — no loss has occurred yet — and a
    superset of that barrier's entry frontier, so re-closing from it at a
    wider capacity reaches the identical closure), or the final carry
    when no overflow happened (budget exhaustion; also exact).  A lane
    resumed from an exact snapshot with a fresh ``lossy`` latch keeps
    full refutation power: False still means "no loss anywhere on the
    accepted path".
    """
    eye_g = jnp.eye(G, dtype=I16)
    slot_mask = slot_onehot.sum(axis=1)

    def tick(carry):
        (t, bptr, state, fok, fcr, alive, failed_at, lossy, peak,
         snapped, bsnap, sst, sfo, sfc, sal) = carry
        bc = jnp.clip(bptr, 0, B - 1)
        done = (bptr >= n_active) | (failed_at >= 0)
        # One closure round at barrier bptr.
        cat_state, cat_fok, cat_fcr, cat_alive, cost = expand_candidates(
            step, eye_g, slot_lane, slot_mask, slot_onehot,
            state, fok, fcr, alive,
            mov_f[bc], mov_v1[bc], mov_v2[bc], mov_open[bc],
            grp_f, grp_v1, grp_v2, grp_open[bc],
        )
        s2, fo2, fc2, a2, ovf, _fp, child = frontier_update_fast(
            cat_state, cat_fok, cat_fcr, cat_alive, cost, F, n_parents=F,
            max_count=mov_f.shape[-1] + 1, dedup_backend=dedup,
        )
        # First overflow: snapshot the PRE-update frontier (exact: lossy
        # is still False) for the next ladder rung to resume from.
        take = ovf & ~snapped & ~lossy & ~done
        snapped2 = snapped | take
        bsnap2 = jnp.where(take, bc, bsnap)
        sst2 = jnp.where(take, state, sst)
        sfo2 = jnp.where(take, fok, sfo)
        sfc2 = jnp.where(take, fcr, sfc)
        sal2 = jnp.where(take, alive, sal)
        # frontier_update_fast domination-prunes its own 2C buffer, so a2
        # already marks a duplicate-free antichain (the "+5 resolved
        # histories at cap 128" benefit lives there) — no outer prune
        # (advisor r3: the doubled prune bought zero alive change).
        stable = ~(a2 & child).any()
        # At the fixpoint: only configs that fired the returning op
        # survive; its slot bit retires; the barrier pointer advances.
        lane = bar_slot[bc] // 32
        bitmask = U32(1) << (bar_slot[bc] % 32).astype(U32)
        lane_vals = jnp.take_along_axis(fo2, jnp.full((F, 1), lane), axis=1)[:, 0]
        a3 = a2 & ((lane_vals & bitmask) != 0)
        clear = jnp.where(jnp.arange(W) == lane, bitmask, U32(0))
        fo3 = fo2 & ~clear[None, :]
        # The return filter subsets an antichain and the uniform bit clear
        # preserves it (all survivors held the bit), so a3/fo3 need no
        # reaping; they are used only on the ticks that advance.
        adv = stable & ~done
        state2 = jnp.where(done, state, s2)
        fok2 = jnp.where(done[None], fok, jnp.where(adv, fo3, fo2))
        fcr2 = jnp.where(done, fcr, fc2)
        alive2 = jnp.where(done, alive, jnp.where(adv, a3, a2))
        failed2 = jnp.where(adv & ~a3.any() & ~lossy, bc, failed_at)
        # a lossy lane can't refute: record no failure, report unknown
        failed2 = jnp.where(adv & ~a3.any() & lossy, jnp.int32(B + 1), failed2)
        bptr2 = jnp.where(adv, bptr + 1, bptr)
        lossy2 = lossy | (ovf & ~done)
        peak2 = jnp.maximum(peak, alive2.sum())
        return (t + 1, bptr2, state2, fok2, fcr2, alive2, failed2, lossy2,
                peak2, snapped2, bsnap2, sst2, sfo2, sfc2, sal2)

    def cont(carry):
        t, bptr, _s, _fo, _fc, _a, failed_at = carry[:7]
        running = (bptr < n_active) & (failed_at < 0)
        return (t < T) & running

    carry0 = (jnp.int32(0), jnp.asarray(bptr0, I32), state0, fok0, fcr0,
              alive0, jnp.int32(-1), jnp.bool_(False),
              jnp.maximum(alive0.sum(), 1).astype(I32),
              jnp.bool_(False), jnp.asarray(bptr0, I32),
              state0, fok0, fcr0, alive0)
    (_t, bptr, state, fok, fcr, alive, failed_at, lossy, peak,
     snapped, bsnap, sst, sfo, sfc, sal) = jax.lax.while_loop(
        cont, tick, carry0
    )
    finished = bptr >= n_active
    valid = finished & alive.any()
    # Budget exhaustion (neither finished nor failed) is loss.
    lossy_out = lossy | (~finished & (failed_at < 0)) | (failed_at > B)
    failed_out = jnp.where(failed_at > B, jnp.int32(-1), failed_at)
    # No overflow snapshot -> resume from the final carry (exact: the
    # lane simply ran out of ticks mid-search).
    bsnap = jnp.where(snapped, bsnap, bptr)
    sst = jnp.where(snapped, sst, state)
    sfo = jnp.where(snapped, sfo, fok)
    sfc = jnp.where(snapped, sfc, fcr)
    sal = jnp.where(snapped, sal, alive)
    return valid, failed_out, lossy_out, peak, bsnap, sst, sfo, sfc, sal


_run_async = functools.partial(
    jax.jit, static_argnames=("step", "F", "T", "B", "P", "G", "W", "dedup")
)(_run_core_async)

#: (step, F, T, B, P, G, W, dedup) -> jitted vmapped async runner.
_ASYNC_RUNNERS: dict = {}


def async_runner(step, F: int, T: int, B: int, P: int, G: int, W: int,
                 dedup: str = "sort"):
    """jit(vmap(_run_core_async)) — the batched async-tick checker.

    Batched inputs (leading lane axis): bptr0, state0, fok0, fcr0,
    alive0 (the resume frontier — see fresh_frontier for stage one),
    n_active, then the 12 barrier/mover/group tables; slot tables
    broadcast.  ``dedup`` selects the per-round dedup backend."""
    key = (step, F, T, B, P, G, W, dedup)
    _cache_counter(_ASYNC_RUNNERS, key, "async")
    if key not in _ASYNC_RUNNERS:
        core = functools.partial(
            _run_core_async, step, F, T, B, P, G, W, dedup=dedup
        )
        axes = (0,) * 18 + (None, None)
        _ASYNC_RUNNERS[key] = faults.AheadOfTime(jax.jit(jax.vmap(core, in_axes=axes)))
    return _ASYNC_RUNNERS[key]


def fresh_frontier(n: int, F: int, W: int, G: int, init_states):
    """Stage-one resume inputs for ``n`` lanes: barrier 0, one alive
    config per lane holding the lane's initial state."""
    bptr0 = np.zeros(n, np.int32)
    state0 = np.zeros((n, F), np.int32)
    state0[:] = np.asarray(init_states, np.int32)[:, None]
    fok0 = np.zeros((n, F, W), np.uint32)
    fcr0 = np.zeros((n, F, G), np.int16)
    alive0 = np.zeros((n, F), bool)
    alive0[:, 0] = True
    return bptr0, state0, fok0, fcr0, alive0


def pad_resume(resume, F: int, W: int, G: int):
    """Re-bucket one lane's saved (bsnap, state, fok, fcr, alive) resume
    frontier to the next stage's (F, W, G).  Growing pads with dead rows
    / zero columns; shrinking is safe because a history's own slots and
    groups always fit its OWN (P, G) — bucket padding beyond them is
    never set (see pad_packed)."""
    bsnap, st, fo, fc, al = resume
    F0, W0 = fo.shape
    G0 = fc.shape[1]
    n_alive = int(al.sum())
    assert n_alive <= F, f"resume frontier {n_alive} exceeds capacity {F}"
    out_st = np.zeros(F, np.int32)
    out_fo = np.zeros((F, W), np.uint32)
    out_fc = np.zeros((F, G), np.int16)
    out_al = np.zeros(F, bool)
    k = min(F0, F)
    out_st[:k] = st[:k]
    out_fo[:k, : min(W0, W)] = fo[:k, : min(W0, W)]
    out_fc[:k, : min(G0, G)] = fc[:k, : min(G0, G)]
    out_al[:k] = al[:k]
    if F < F0 and al[F:].any():
        # compact alive rows first instead of truncating live configs
        sel = np.flatnonzero(al)[:F]
        out_st[: len(sel)] = st[sel]
        out_fo[: len(sel), : min(W0, W)] = fo[sel][:, : min(W0, W)]
        out_fc[: len(sel), : min(G0, G)] = fc[sel][:, : min(G0, G)]
        out_al[:] = False
        out_al[: len(sel)] = True
    return int(bsnap), out_st, out_fo, out_fc, out_al


# ---------------------------------------------------------------------------
# Greedy witness walk: one config, fire-returning-op-first, one-enabler
# lookahead — the device-side equivalent of the CPU DFS's greedy path
# ---------------------------------------------------------------------------


def _greedy_core(
    step,
    B: int,
    P: int,
    G: int,
    W: int,
    init_state,
    n_active,
    bar_f,
    bar_v1,
    bar_v2,
    bar_slot,
    mov_f,
    mov_v1,
    mov_v2,
    mov_open,
    grp_f,
    grp_v1,
    grp_v2,
    grp_open,
    slot_lane,
    slot_onehot,
):
    """Walk ONE configuration through all barriers, greedily.

    The CPU DFS resolves valid histories by its greedy path — fire the
    returning op first, backtracking only when stuck
    (wgl_cpu.dfs_analysis; knossos's observation that valid histories
    "usually walk straight through").  This kernel is that path as a
    fixed-shape ``lax.scan``: per barrier, fire the returning op
    directly if legal, else fire ONE enabling move (an open ok op or a
    crashed-group op) whose step makes the returning op legal — a
    two-step lookahead over all P+G movers, evaluated as one vectorized
    step batch — else the walk is STUCK and escalates.

    Every applied transition is legal, so completion is a constructive
    witness: ``True`` is exact.  The walk never refutes — stuck means
    "unknown", it proves nothing (a frontier/DFS engine decides).  Cost
    is O(B·(P+G)) scalar step evaluations with no frontier buffers at
    all — the cheapest possible first rung, and the shape that resolves
    BASELINE config 2 (10k-op valid register) on-device.

    Returns (finished, stuck_at, fired_crashed_total):
    ``stuck_at`` = barrier index where the walk stuck (-1 = never).
    """
    slot_mask = slot_onehot.sum(axis=1)  # [P] uint32 in-lane bit
    p_iota = jnp.arange(P, dtype=I32)

    def barrier(carry, xs):
        state, fok, fcr, stuck_at = carry
        b_idx, bf, bv1, bv2, bslot, mf, mv1, mv2, mopen, gopen = xs
        done = (stuck_at >= 0) | (b_idx >= n_active)
        lane = bslot // 32
        bit = (U32(1) << (bslot % 32).astype(U32))
        has_bit = (fok[lane] & bit) != 0
        # Case A: already fired as an earlier barrier's enabler — retire.
        # Case B: direct fire.
        s1, legal1 = step(state, bf, bv1, bv2)
        # Case C: one enabling open ok op, then the returning op.
        already = (jnp.take(fok, slot_lane) & slot_mask) != 0  # [P]
        ps2, plegal = step(state, mf, mv1, mv2)
        ps3, plegal3 = step(ps2, bf, bv1, bv2)
        pcand = plegal & plegal3 & mopen & ~already & (p_iota != bslot)
        # Case D: one enabling crashed-group op, then the returning op.
        gs2, glegal = step(state, grp_f, grp_v1, grp_v2)
        gs3, glegal3 = step(gs2, bf, bv1, bv2)
        gcand = glegal & glegal3 & (fcr < gopen) & (gs2 != state)
        p_any = pcand.any()
        g_any = gcand.any()
        p_idx = jnp.argmax(pcand)
        g_idx = jnp.argmax(gcand)
        clear = jnp.where(jnp.arange(W) == lane, bit, U32(0))
        # Priority: A (no step) > B (direct) > C (ok enabler) > D (group).
        new_state = jnp.where(
            has_bit, state,
            jnp.where(legal1, s1,
                      jnp.where(p_any, ps3[p_idx], gs3[g_idx])))
        new_fok = jnp.where(
            has_bit, fok & ~clear,
            jnp.where(legal1, fok,
                      jnp.where(p_any, fok | slot_onehot[p_idx], fok)))
        new_fcr = jnp.where(
            ~has_bit & ~legal1 & ~p_any & g_any,
            fcr + (jnp.arange(G) == g_idx).astype(I16), fcr)
        ok = has_bit | legal1 | p_any | g_any
        stuck2 = jnp.where(~done & ~ok, b_idx, stuck_at)
        keep = done | ~ok
        state2 = jnp.where(keep, state, new_state)
        fok2 = jnp.where(keep, fok, new_fok)
        fcr2 = jnp.where(keep, fcr, new_fcr)
        return (state2, fok2, fcr2, stuck2), None

    carry0 = (
        jnp.asarray(init_state, I32),
        jnp.zeros(W, U32),
        jnp.zeros(G, I16),
        jnp.int32(-1),
    )
    xs = (
        jnp.arange(B, dtype=I32), bar_f, bar_v1, bar_v2, bar_slot,
        mov_f, mov_v1, mov_v2, mov_open, grp_open,
    )
    (state, fok, fcr, stuck_at), _ = jax.lax.scan(barrier, carry0, xs)
    finished = stuck_at < 0
    return finished, stuck_at, fcr.sum().astype(I32)


_greedy = functools.partial(
    jax.jit, static_argnames=("step", "B", "P", "G", "W")
)(_greedy_core)

#: (step, B, P, G, W) -> jitted vmapped greedy runner.
_GREEDY_RUNNERS: dict = {}


def greedy_runner(step, B: int, P: int, G: int, W: int):
    """jit(vmap(_greedy_core)) — the batched greedy witness walk."""
    key = (step, B, P, G, W)
    _cache_counter(_GREEDY_RUNNERS, key, "greedy")
    if key not in _GREEDY_RUNNERS:
        core = functools.partial(_greedy_core, step, B, P, G, W)
        axes = (0,) * 14 + (None, None)
        _GREEDY_RUNNERS[key] = faults.AheadOfTime(jax.jit(jax.vmap(core, in_axes=axes)))
    return _GREEDY_RUNNERS[key]


def greedy_analysis(
    model: m.Model,
    history: Sequence[dict],
    max_groups: int = 64,
    max_procs: int = 128,
) -> dict:
    """Single-history greedy witness walk.  ``True`` (with a witness) or
    ``"unknown"`` — never ``False`` (see _greedy_core)."""
    try:
        packed = pack(model, history)
    except NotTensorizable as e:
        return {"valid?": "unknown", "cause": f"not tensorizable: {e}"}
    if packed["B"] == 0:
        return {"valid?": True}
    if packed["G"] > max_groups:
        return {"valid?": "unknown", "cause": f"{packed['G']} crashed-op groups exceeds {max_groups}"}
    if packed["P"] > max_procs:
        return {"valid?": "unknown", "cause": f"{packed['P']} process slots exceeds {max_procs}"}
    n_active = int(packed["bar_active"].sum())
    packed = pad_packed(packed)
    finished, stuck_at, fired = _greedy(
        packed["step"],
        packed["B"],
        packed["P"],
        packed["G"],
        packed["W"],
        packed["init_state"],
        np.int32(n_active),
        *packed["bar"],
        *packed["mov"],
        *packed["grp"],
        packed["grp_open"],
        jnp.asarray(packed["slot_lane"]),
        jnp.asarray(packed["slot_onehot"]),
    )
    stats = {"engine": "greedy", "fired-crashed": int(fired)}
    if bool(finished):
        return {"valid?": True, "kernel": stats}
    return {
        "valid?": "unknown",
        "cause": "greedy walk stuck (no single-enabler move)",
        "op": history[int(packed["bar_opid"][int(stuck_at)])],
        "kernel": {**stats, "stuck-at": int(stuck_at)},
    }


def analysis_async(
    model: m.Model,
    history: Sequence[dict],
    capacity: int = 128,
    ticks: int | None = None,
    max_groups: int = 64,
    max_procs: int = 128,
    dedup_backend: str | None = None,
) -> dict:
    """Single-history front-end for the async-tick kernel (testing and
    shape exploration; the batched path drives async_runner directly)."""
    dedup = resolve_dedup_backend(dedup_backend)
    try:
        packed = pack(model, history)
    except NotTensorizable as e:
        return {"valid?": "unknown", "cause": f"not tensorizable: {e}"}
    if packed["B"] == 0:
        return {"valid?": True}
    if packed["G"] > max_groups:
        return {"valid?": "unknown", "cause": f"{packed['G']} crashed-op groups exceeds {max_groups}"}
    if packed["P"] > max_procs:
        return {"valid?": "unknown", "cause": f"{packed['P']} process slots exceeds {max_procs}"}
    n_active = int(packed["bar_active"].sum())
    packed = pad_packed(packed)
    B = packed["B"]
    T = int(ticks) if ticks is not None else async_ticks(B)
    F, W, G = int(capacity), packed["W"], packed["G"]
    t0 = time.perf_counter()
    bptr0, st0, fo0, fc0, al0 = fresh_frontier(
        1, F, W, G, [packed["init_state"]]
    )
    valid, failed_at, lossy, peak, _bs, _st, _fo, _fc, _al = _run_async(
        packed["step"],
        F,
        T,
        B,
        packed["P"],
        G,
        W,
        bptr0[0],
        st0[0],
        fo0[0],
        fc0[0],
        al0[0],
        np.int32(n_active),
        *packed["bar"],
        *packed["mov"],
        *packed["grp"],
        packed["grp_open"],
        jnp.asarray(packed["slot_lane"]),
        jnp.asarray(packed["slot_onehot"]),
        dedup=dedup,
    )
    valid = bool(valid)
    failed_at = int(failed_at)
    lossy = bool(lossy)
    stats = {"frontier-peak": int(peak), "capacity": int(capacity), "ticks": T, "lossy?": lossy}
    obs.span_event(
        "wgl.async", time.perf_counter() - t0, valid=valid, lossy=lossy,
        peak_frontier=int(peak), capacity=int(capacity), ticks=T, dedup=dedup,
    )
    if valid:
        return {"valid?": True, "kernel": stats}
    if not lossy:
        op = None
        if 0 <= failed_at < len(packed["bar_opid"]):
            op_pos = int(packed["bar_opid"][failed_at])
            op = history[op_pos]
            # POSITIONAL id (invoke position in the history, the identity
            # sweep_analysis's stop_at_index matches) — op.get("index") is
            # a user-facing field that may differ on unindexed or
            # re-indexed histories (advisor r4).
            stats["bar-opid"] = op_pos
        return {"valid?": False, "op": op, "kernel": stats}
    return {
        "valid?": "unknown",
        "cause": "frontier capacity or tick budget exhausted",
        "kernel": stats,
    }
