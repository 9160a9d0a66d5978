"""JAX round kernels for the structure-of-arrays Monte-Carlo backend.

This module holds the device side of :mod:`repro.core.sim.soa`: a
``jax.jit``-compiled loop that advances **R runs of one scenario
skeleton simultaneously** through discrete scheduling rounds.  The host
(:func:`repro.core.sim.soa.build_problem`) precomputes everything that
is lane-independent — the round grid (seam-aligned), per-round job
windows over the release-sorted job axis, EDF permutations, per-segment
schedule bindings, hot-swap capacities/staging volumes — and the kernel
only does the lane-dependent part as fused array ops over ``(R, W)``
windows:

* readiness via *finish codes*: every job resolves to one float in a
  ``(R, n_jobs + n_sensors + 1)`` code array (``+inf`` unresolved,
  ``t`` clean finish at ``t``, ``-t - 1`` degraded/dropped at ``t``),
  so dependency propagation is a single gather;
* *backdated exact event times*: rounds only decide **that** something
  happens, the times themselves (ready/start/finish/drop) are computed
  exactly from the inputs, so chain latencies carry round-quantization
  noise only through changed *decisions*, not through time rounding;
  tp_driven's decisions are taken at its queue-change instants inside
  each round, in time order, so it carries none;
* policy decisions (cyc / cyc_s / tp_driven / ads_tile) re-expressed as
  masked ladder/EDF array ops (see ``_alloc_ladder``), with the
  engine's quota semantics: ``grant = largest candidate <=
  min(want, tiles_left)`` where ``want`` is the smallest candidate
  meeting the deadline (``fit_quota`` equivalence);
* schedule hot-swaps as a ``lax.cond`` seam step (capacity switch,
  vectorized largest-first preemption, staging bytes precomputed on the
  host).

Everything is float32; the absolute times in a <=2 s horizon keep
~1e-7 s resolution, far below the multi-ms effects under study.  A
resize scales that error by its ratio of durations, though, so a
decision whose margin is a few microseconds can go the other way than
in the scalar engine's float64, and its drive leave the scalar's
trajectory.  tp_driven, whose walks turn on such margins at every queue
change, runs each round on times relative to the round's start and
carries what float32 rounded away from its finish, sync and stall times
(see ``rel_of``).  The contract with the scalar engine is
**distributional** (KS + CI overlap + exact structural invariants),
enforced by
``benchmarks.check_equivalence --mode distributional`` — see
``docs/performance.md#soa-backend`` for what is and is not guaranteed.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
from functools import partial
from types import SimpleNamespace
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from ...obs import metrics

__all__ = [
    "KernelConfig",
    "NFIELDS",
    "F_STATE",
    "F_READY",
    "F_DEG",
    "F_START",
    "F_FIN",
    "F_DOP",
    "F_PART",
    "F_REM",
    "F_SUB",
    "F_TGT",
    "PEND",
    "READY",
    "RUN",
    "DONE",
    "DROP",
    "POLICY_IDS",
    "round_loop",
    "simulate",
    "ladder_grant_reference",
    "clear_kernel_cache",
]

# mutable per-job state: one (R, N, NFIELDS) float32 array so each round
# slides a single (R, W, NFIELDS) window in and out
(
    F_STATE,   # job state code (PEND..DROP)
    F_READY,   # exact ready time (resolve of release + preds)
    F_DEG,     # degraded flag (dropped/degraded predecessor upstream)
    F_START,   # exact (backdated) start time
    F_FIN,     # finish projection while RUNNING; final time once DONE/DROP
    F_DOP,     # currently held tiles
    F_PART,    # partition bound at start
    F_REM,     # remaining work fraction (1 until started; set on preempt)
    F_SUB,     # sub-deadline bound at start (retargets stop at start)
    F_TGT,     # ads slack-shared target bound at start (tp_driven's plane
               # is F_TIMER)
    F_ADV,     # last progress-sync time (start / freeze / stall end): the
               # scalar engine only advances ``job.progress`` at realloc
               # freezes, so its at-risk and quota projections run on
               # progress *stale since this time* — reproduced here
) = range(11)
NFIELDS = 11
#: tp_driven has no target bound: its F_TGT plane holds, under hard drops,
#: the e2e deadline a job's timer fires at (inf where the job armed none)
F_TIMER = F_TGT

PEND, READY, RUN, DONE, DROP = 0.0, 1.0, 2.0, 3.0, 4.0

POLICY_IDS = {"cyc": 0, "cyc_s": 1, "tp_driven": 2, "ads_tile": 3}
_CYC, _CYC_S, _TP, _ADS = 0, 1, 2, 3


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Hashable static configuration of one compiled round loop.

    Everything here participates in the jit cache key; array shapes do
    too (via the traced arguments), so one scenario x policy x (R, dt)
    cell compiles once and is then reused across seed batches.
    """

    policy: int                # POLICY_IDS value
    R: int                     # lanes (runs)
    W: int                     # window width over the job axis
    C: int                     # DoP-candidate ladder width
    PM: int                    # max predecessor in-degree
    P: int                     # partitions
    tile_flops: float
    fixed_s: float
    decision_s: float
    per_hop_s: float
    inv_bw: float              # 1 / migration bandwidth
    realloc_gate: float = 1.0
    admission: bool = True     # ads ablation / cyc ERT gate
    quota_control: bool = True
    #: deadline-drop regime: 0 = none (the runner's default
    #: ``drop_policy="soft"`` arms no e2e timers for tp/ads), 1 =
    #: sub-deadline termination (cyc's unconditional budget
    #: enforcement), 2 = e2e-deadline dequeue (``drop_policy="hard"``)
    drop_mode: int = 0
    #: chunk boundaries per job (SimConfig.n_chunks): the scalar engine
    #: syncs a running job's progress only at its chunk events, so the
    #: ads at-risk projection runs on progress stale by up to one chunk
    #: interval — the kernel reproduces that bounded staleness
    n_chunks: int = 6
    alloc_iters: int = 8       # monotone EDF-allocation refinement steps
    bump_passes: int = 8       # tp work-conserving bump refinement steps
    use_pallas: bool = False   # route _alloc_ladder through Pallas


# ---------------------------------------------------------------------------
# allocation primitives
# ---------------------------------------------------------------------------
def _ladder_grant(limit, cand):
    """Largest candidate DoP <= ``limit`` (0 when none fits).

    ``limit``: (R, W) float tile budget per job; ``cand``: (W, C) or
    (R, W, C) candidate values (padded by repeating the last rung).
    This is the vectorized form of the engine's quota walk: with
    ``limit = min(want, tiles_left)`` it reproduces ``fit_quota``'s
    "smallest candidate meeting the deadline, else the largest that
    fits" exactly.
    """
    ok = cand <= limit[..., None] + 0.5
    return jnp.max(jnp.where(ok, cand, 0.0), axis=-1)


#: lanes per grid step of the Pallas grant.  The job window is the
#: 128-wide lane axis and the ladder the leading axis, so a (C, 512, W)
#: candidate block takes C x 256 KiB of VMEM (W padded to 128 lanes),
#: double-buffered well inside the 16 MiB scoped limit at C = 6.
_GRANT_BLOCK_R = 512


def _ladder_grant_pallas(limit, cand, interpret=False):
    """Pallas version of :func:`_ladder_grant`, bit-identical to it.

    The grid walks blocks of lanes; the C-wide ladder is unrolled as C
    elementwise (lanes, W) planes instead of being reduced along the
    lane axis.  ``cand`` is (W, C), one ladder per job shared by every
    lane (the round loop's case, kept as one resident block), or
    (R, W, C).  ``interpret=True`` runs it without a TPU (tests)."""
    R, W = limit.shape
    C = cand.shape[-1]
    tr = min(R, _GRANT_BLOCK_R)
    if cand.ndim == 2:
        cand_t = cand.T[:, None, :]
        cand_spec = pl.BlockSpec((C, 1, W), lambda i: (0, 0, 0))
    else:
        cand_t = jnp.moveaxis(cand, -1, 0)
        cand_spec = pl.BlockSpec((C, tr, W), lambda i: (0, i, 0))

    def kernel(limit_ref, cand_ref, out_ref):
        lim = limit_ref[...] + 0.5
        out = None
        for c in range(C):
            cd = cand_ref[c]
            g = jnp.where(cd <= lim, cd, 0.0)
            out = g if out is None else jnp.maximum(out, g)
        out_ref[...] = out

    row_spec = pl.BlockSpec((tr, W), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((R, W), limit.dtype),
        grid=(pl.cdiv(R, tr),),
        in_specs=[row_spec, cand_spec],
        out_specs=row_spec,
        interpret=interpret,
    )(limit, cand_t)


def ladder_grant_reference(limit: np.ndarray, cand: np.ndarray) -> np.ndarray:
    """NumPy oracle for the grant select (test hook for jnp vs pallas)."""
    ok = cand <= limit[..., None] + 0.5
    return np.max(np.where(ok, cand, 0.0), axis=-1)


def _pick(table, idx):
    """``table[..., idx]`` per element: ``table`` is ``(..., K)`` and
    broadcasts against ``idx[..., None]``; ``idx`` is an int array in
    ``[0, K)``, clipped by the caller.

    A static chain of K selects over the small minor axis (partitions,
    bins, ladder rungs).  A ``take_along_axis`` with an index that
    varies per lane and per job lowers on a TPU to an element-by-element
    gather over the whole ``(R, W)`` window; the selects stay on the
    vector unit, and copy the same value bit for bit (``inf`` and
    booleans included, which a one-hot product would not)."""
    out = jnp.broadcast_to(
        table[..., 0], jnp.broadcast_shapes(table.shape[:-1], idx.shape)
    )
    for k in range(1, table.shape[-1]):
        out = jnp.where(idx == k, table[..., k], out)
    return out


def _class_prefix(cfg, part_s, cap_p, dtype):
    """Per-partition queue-prefix operators for one sorted queue.

    Returns ``(excl, total, capg)``: ``excl(d)`` is each entry's
    exclusive prefix sum of ``d`` over earlier same-partition entries,
    ``total(d)`` the inclusive whole-partition sum seen by each entry,
    and ``capg`` the entry's own partition budget.

    Each partition's sums run over a one-hot (R, P, W) split of ``d``
    (the window stays the minor axis): O(P W) per lane, so a window
    widened to the whole horizon stays affordable.  ``d`` holds tile
    counts, integers that f32 adds exactly in any order, so the result
    does not depend on how the backend orders or tiles the sums (a
    matmul form would, on a TPU: its default f32 precision is one bf16
    pass)."""
    part_i = jnp.clip(part_s.astype(jnp.int32), 0, cfg.P - 1)
    ar_p = jnp.arange(cfg.P, dtype=jnp.int32)[None, :, None]
    onehot = (part_i[:, None, :] == ar_p).astype(dtype)
    capg = _pick(cap_p[:, None, :], part_i)

    def excl(d):
        x = onehot * d[:, None, :]
        return jnp.sum((jnp.cumsum(x, axis=2) - x) * onehot, axis=1)

    def total(d):
        tot = jnp.sum(onehot * d[:, None, :], axis=2)
        return _pick(tot[:, None, :], part_i)

    return excl, total, capg


def _alloc_ladder(cfg, want, entry, part_s, cand_s, cap_p):
    """Feasible EDF ladder allocation over one round's sorted queue.

    ``want``: (R, W) desired DoP per queue entry (EDF order);
    ``entry``: (R, W) bool participation mask; ``part_s``: (R, W)
    partition id per entry; ``cand_s``: (W, C) candidate rows;
    ``cap_p``: (R, P) tile budget per partition.

    The scalar engine walks the queue sequentially, each entry seeing
    the tiles left by its predecessors.  Here a monotone fixed-point
    iteration replaces the walk: start from ``want``, compute each
    entry's exclusive prefix load per partition, re-grant against
    ``min(want, left)``, repeat.  Grants only ever shrink, so the
    result is always feasible; ``alloc_iters`` bounds how much
    freed-by-predecessor capacity later entries can recover (the
    documented approximation vs the exact walk).
    """
    want = jnp.where(entry, want, 0.0)
    cur = want
    sel = _ladder_grant_pallas if cfg.use_pallas else _ladder_grant
    # the per-partition exclusive prefix: "tiles my EDF predecessors in
    # my partition already took"
    excl, _, capg = _class_prefix(cfg, part_s, cap_p, want.dtype)

    def step(cur):
        cume = excl(cur)
        return jnp.where(
            entry, sel(jnp.minimum(want, capg - cume), cand_s), 0.0
        )

    # the refinement map is a pure function of ``cur``: once an
    # application leaves it unchanged every further one would too, so a
    # convergence-gated while_loop is exactly the unrolled loop (the
    # fixed point is usually reached in 2-3 steps; ``alloc_iters``
    # stays the worst-case bound)
    def cond(c):
        i, cur, prev = c
        return (i < cfg.alloc_iters) & jnp.any(cur != prev)

    def it(c):
        i, cur, _ = c
        return i + 1, step(cur), cur

    _, cur, _ = lax.while_loop(cond, it, (0, step(want), want + 1.0))
    return cur


def _bump_work_conserving(cfg, grant, entry, part_s, cand_s, cap_p):
    """tp_driven's saturation pass: spend leftover tiles by bumping
    queue entries (EDF order) to their next candidate rung, one rung per
    entry per pass, as the scalar ``while bumped`` loop sweeps its
    queue."""
    excl, total, capg = _class_prefix(cfg, part_s, cap_p, grant.dtype)
    n_entries = grant.shape[-1]

    def one_pass(grant):
        above = cand_s > grant[..., None] + 0.5
        nxt = jnp.min(jnp.where(above, cand_s, jnp.inf), axis=-1)
        delta = jnp.where(entry & jnp.isfinite(nxt), nxt - grant, 0.0)
        leftg = capg - total(grant)

        # the sweep takes an entry's bump where it fits in what earlier
        # takers left, and offers a skipped entry's tiles to later ones:
        # each entry's choice depends on earlier ones only, so iterating
        # the choice reaches that sweep's unique fixed point, the first
        # k entries exact after k steps
        def moved(c):
            i, take, prev = c
            return (i < n_entries) & jnp.any(take != prev)

        def choose(c):
            i, take, _ = c
            cume = excl(jnp.where(take, delta, 0.0))
            return i + 1, (delta > 0) & (cume + delta <= leftg + 0.5), take

        take0 = delta > 0
        _, take, _ = lax.while_loop(moved, choose, (0, take0, ~take0))
        return jnp.where(take, grant + delta, grant)

    # same convergence argument as the ladder: a pass that changes
    # nothing makes every further pass a no-op
    def cond(c):
        i, grant, prev = c
        return (i < cfg.bump_passes) & jnp.any(grant != prev)

    def it(c):
        i, grant, _ = c
        return i + 1, one_pass(grant), grant

    _, grant, _ = lax.while_loop(cond, it, (0, one_pass(grant), grant - 1.0))
    return grant


# ---------------------------------------------------------------------------
# the round loop
# ---------------------------------------------------------------------------
def _build_loop(cfg: KernelConfig, const: Dict[str, "jnp.ndarray"]):
    R, W, P, C, PM = cfg.R, cfg.W, cfg.P, cfg.C, cfg.PM
    tf = cfg.tile_flops
    pol = cfg.policy
    n_rounds = int(const["t0"].shape[0])
    S_ = int(const["caps"].shape[0])

    def dur(work, io, sync, c):
        cc = jnp.maximum(c, 1.0)
        return work / (cc * tf) + io + sync * (cc - 1.0)

    def seam_step(op):
        """Schedule hot-swap at a segment-entry round (time = t0):
        capacity switch, largest-first preemption down to the new caps,
        one stop-migrate-restart stall per partition charged with the
        host-precomputed staging volume plus preempted checkpoints."""
        (state, fin, dop, rem, adv, pborn, stall_end, nre, rbytes,
         t0, workw, iow, syncw, ckptw, capsg, hopsg, stagedg) = op
        run = state == RUN
        d_cur = dur(workw, iow, syncw, dop)
        pos = jnp.arange(W, dtype=jnp.float32)
        moved = jnp.zeros((R, P), dtype=jnp.float32)
        vict = jnp.zeros((R, W), dtype=bool)
        for p in range(P):
            mp = run & (pborn == p)
            dv = jnp.where(mp, dop, 0.0)
            over = jnp.sum(dv, axis=1) - capsg[p]
            # removal order: largest dop first, later jid first on ties
            key = -(dv * (W + 1.0) + pos[None, :])
            order = jnp.argsort(key, axis=1)
            inv = jnp.argsort(order, axis=1)
            dsort = jnp.take_along_axis(dv, order, axis=1)
            cume = jnp.cumsum(dsort, axis=1) - dsort
            v_sorted = (dsort > 0) & (cume < over[:, None] - 1e-6)
            vp = jnp.take_along_axis(v_sorted, inv, axis=1)
            vict = vict | vp
            moved = moved.at[:, p].add(
                stagedg[p] + jnp.sum(jnp.where(vp, ckptw * dop, 0.0), axis=1)
            )
        stall = (
            cfg.fixed_s + cfg.decision_s + hopsg[None, :] * cfg.per_hop_s
            + moved * cfg.inv_bw
        )
        stall_end = jnp.maximum(stall_end, t0 + stall)
        # preempted: back to READY with exact residual fraction
        rem = jnp.where(
            vict, jnp.clip((fin - t0) / jnp.maximum(d_cur, 1e-12), 0.0, 1.0), rem
        )
        state = jnp.where(vict, READY, state)
        dop = jnp.where(vict, 0.0, dop)
        fin = jnp.where(vict, jnp.inf, fin)
        # freeze survivors for their partition's stall
        stall_own = jnp.sum(
            jnp.stack([
                jnp.where(pborn == p, stall[:, p][:, None], 0.0)
                for p in range(P)
            ]),
            axis=0,
        )
        still = (state == RUN)
        fin = jnp.where(still, fin + stall_own, fin)
        adv = jnp.where(still, t0 + stall_own, adv)
        nre = nre + jnp.float32(P)
        rbytes = rbytes + jnp.sum(moved, axis=1)
        return state, fin, dop, rem, adv, stall_end, nre, rbytes

    def window(r, st):
        """Round ``r``: its times, the (R, W) window of each state plane,
        and the job and segment constants over that window."""
        sg = const["seg"][r]
        lo = const["lo"][r]

        def per_seg(name):
            return lax.dynamic_slice(const[name], (sg, lo), (1, W))[0]

        def per_part_seg(name):
            return lax.dynamic_slice(const[name], (sg, 0), (1, P))[0]

        return SimpleNamespace(
            r=r, t0=const["t0"][r], t1=const["t1"][r], sg=sg, lo=lo,
            # ``st`` is a tuple of NFIELDS separate (R, N) planes: updating
            # a (R, W) window of each is in-place under the fori_loop,
            # whereas a packed (R, N, NFIELDS) array made XLA:CPU copy the
            # whole state every round (~7x the slice cost)
            planes=tuple(lax.dynamic_slice(a, (0, lo), (R, W)) for a in st),
            relw=lax.dynamic_slice(const["release"], (lo,), (W,)),
            e2ew=lax.dynamic_slice(const["e2e"], (lo,), (W,)),
            syncw=lax.dynamic_slice(const["sync"], (lo,), (W,)),
            ckptw=lax.dynamic_slice(const["ckpt"], (lo,), (W,)),
            predw=lax.dynamic_slice(const["preds"], (lo, 0), (W, PM)),
            workw=lax.dynamic_slice(const["work"], (0, lo), (R, W)),
            iow=lax.dynamic_slice(const["io"], (0, lo), (R, W)),
            ertw=per_seg("ert"),
            subw=per_seg("sub"),
            tgtw=per_seg("tgt"),
            pdw=per_seg("pdop"),
            parw=per_seg("part"),
            candw=lax.dynamic_slice(const["cands"], (sg, lo, 0), (1, W, C))[0],
            capsg=per_part_seg("caps"),
            hopsg=per_part_seg("hops"),
            stagedg=per_part_seg("staged"),
            permr=const["perm"][r],
            ipermr=const["iperm"][r],
        )

    def pack(st, lo, planes):
        return tuple(
            lax.dynamic_update_slice(a, p, (0, lo)) for a, p in zip(st, planes)
        )

    def seam(w, state, fin, dop, rem, adv, pborn, stall_end, nre, rbytes,
             t0=None):
        """The schedule hot-swap of a segment-entry round (rare), else
        the planes as they are; times are relative to ``t0`` when given."""
        do_swap = const["entry"][w.r] & const["swap"][w.sg]
        return lax.cond(
            do_swap,
            seam_step,
            lambda op: (op[0], op[1], op[2], op[3], op[4], op[6], op[7], op[8]),
            (state, fin, dop, rem, adv, pborn, stall_end, nre, rbytes,
             w.t0 if t0 is None else t0, w.workw, w.iow, w.syncw, w.ckptw,
             w.capsg, w.hopsg, w.stagedg),
        )

    # ---- policy helpers, over one round's window ---------------------------
    def ladder_durations(w):
        """(R, W, C) duration of each job at each rung of its ladder."""
        return (
            w.workw[..., None] / (jnp.maximum(w.candw, 1.0)[None, :, :] * tf)
            + w.iow[..., None]
            + w.syncw[None, :, None] * jnp.maximum(w.candw - 1.0, 0.0)[None, :, :]
        )

    def want_of(w, d_lad, rem_f, slack):
        """fit_quota's ladder target with no tile cap (cap folds in
        at grant time): smallest candidate meeting the deadline,
        else the largest rung."""
        if not cfg.quota_control:
            return jnp.broadcast_to(w.candw[None, :, -1], (R, W))
        meet = rem_f[..., None] * d_lad <= slack[..., None] + 1e-12
        first = jnp.argmax(meet, axis=-1)
        anym = jnp.any(meet, axis=-1)
        picked = _pick(w.candw[None], first)
        return jnp.where(anym, picked, w.candw[None, :, -1])

    def edf_alloc(w, want_m, entry_m, part_m, cand_rows, pool, bump=False):
        """EDF-permute, ladder-allocate, inverse-permute."""
        with jax.named_scope("alloc"):
            want_s = jnp.take(want_m, w.permr, axis=1)
            entry_s = jnp.take(entry_m, w.permr, axis=1)
            part_s = jnp.take(part_m, w.permr, axis=1)
            cand_s = (
                jnp.take(cand_rows, w.permr, axis=0)
                if cand_rows.ndim == 2
                else cand_rows
            )
            grant_s = _alloc_ladder(cfg, want_s, entry_s, part_s, cand_s, pool)
            if bump:
                grant_s = _bump_work_conserving(
                    cfg, grant_s, entry_s, part_s, cand_s, pool
                )
            return jnp.take(grant_s, w.ipermr, axis=1)

    def per_part(mask, val=None):
        """(R, P) per-partition sum (or any) keyed by an id array."""
        m, ids = mask
        ar_p = jnp.arange(P, dtype=jnp.int32)
        oh = jnp.broadcast_to(ids, (R, W))[..., None] == ar_p
        if val is None:
            return jnp.any(m[..., None] & oh, axis=1)
        v = jnp.broadcast_to(val, (R, W))
        return jnp.sum(
            jnp.where(m[..., None] & oh, v[..., None], 0.0), axis=1
        )

    def own_of(arr_p, idx_i, padval):
        pad = jnp.full((R, 1), padval, dtype=arr_p.dtype)
        return _pick(
            jnp.concatenate([arr_p, pad], axis=1)[:, None, :],
            jnp.clip(idx_i, 0, P),
        )

    def body(r, carry):
        """One round of cyc / cyc_s / ads_tile: decisions at ``w.t1``."""
        st, codes, stall_end, busy, rel, nre, rbytes, dwork = carry
        with jax.named_scope("window"):
            w = window(r, st)
            (state, ready_t, deg, start, fin, dop, pborn, rem, subb, tgtb,
             adv) = w.planes

            d_cur = dur(w.workw, w.iow, w.syncw, dop)

        with jax.named_scope("step"):
            # ---- seam hot-swap (rare; only at segment-entry rounds) ------
            state, fin, dop, rem, adv, stall_end, nre, rbytes = seam(
                w, state, fin, dop, rem, adv, pborn, stall_end, nre, rbytes
            )
            d_cur = dur(w.workw, w.iow, w.syncw, dop)

            # ---- finishes ------------------------------------------------
            # drop_mode 1: cyc's unconditional budget enforcement at the
            # bound sub-deadline; drop_mode 2: hard e2e-deadline dequeue;
            # drop_mode 0 (the runner's soft default): late jobs finish late
            run = state == RUN
            if cfg.drop_mode == 1:
                lim_run = subb
            elif cfg.drop_mode == 2:
                lim_run = jnp.broadcast_to(w.e2ew[None, :], (R, W))
            else:
                lim_run = jnp.full((R, W), jnp.inf, dtype=jnp.float32)
            drop_run = run & (lim_run <= w.t1) & (fin > lim_run + 1e-9)
            done_now = run & (fin <= w.t1) & ~drop_run
            state = jnp.where(done_now, DONE, state)

            # ---- readiness (release passed + all predecessors resolved) --
            pend = state == PEND
            pcodes = codes[:, w.predw.reshape(-1)].reshape(R, W, PM)
            unresolved = jnp.any(jnp.isinf(pcodes), axis=-1)
            rtimes = jnp.where(pcodes < 0, -pcodes - 1.0, pcodes)
            res_t = jnp.maximum(w.relw[None, :], jnp.max(rtimes, axis=-1))
            newready = pend & (w.relw[None, :] <= w.t1) & ~unresolved
            state = jnp.where(newready, READY, state)
            ready_t = jnp.where(newready, res_t, ready_t)
            deg = jnp.where(newready, jnp.any(pcodes < -0.5, axis=-1), deg)

            # ---- deadline drops (exact drop times, backdated) ------------
            if cfg.drop_mode == 1:
                lim_rdy = jnp.broadcast_to(w.subw[None, :], (R, W))
            elif cfg.drop_mode == 2:
                lim_rdy = jnp.broadcast_to(w.e2ew[None, :], (R, W))
            else:
                lim_rdy = jnp.full((R, W), jnp.inf, dtype=jnp.float32)
            rdy = state == READY
            drop_rdy = rdy & (lim_rdy <= w.t1)
            droptime = jnp.where(
                drop_run, lim_run, jnp.maximum(lim_rdy, ready_t)
            )
            dropping = drop_run | drop_rdy
            rem_d = jnp.where(
                drop_run,
                jnp.clip((fin - droptime) / jnp.maximum(d_cur, 1e-12), 0.0, 1.0),
                rem,
            )
            d_plan = dur(w.workw, w.iow, w.syncw, w.pdw[None, :])
            dwork = dwork + jnp.sum(
                jnp.where(dropping, rem_d * d_plan * w.pdw[None, :], 0.0), axis=1
            )
            state = jnp.where(dropping, DROP, state)
            fin = jnp.where(dropping, droptime, fin)
            deg = jnp.where(dropping, 1.0, deg)

            # in-round capacity-release times per partition: a job that sat
            # queued through earlier rounds can only start at the event that
            # made room (a completion or drop), never back at its admission
            # time — the scalar starts it from that event's callback
            fpart = jnp.where(drop_rdy, w.parw[None, :], pborn).astype(jnp.int32)
            freeing = done_now | dropping
            ar_p = jnp.arange(P, dtype=jnp.int32)
            freed_t_p = jnp.max(
                jnp.where(
                    freeing[..., None] & (fpart[..., None] == ar_p),
                    fin[..., None], w.t0,
                ),
                axis=1,
            )

            # ---- finish codes (idempotent re-derivation for the window) --
            terminal = state >= DONE
            code_w = jnp.where(
                terminal, jnp.where(deg > 0.5, -fin - 1.0, fin), jnp.inf
            )
            codes = lax.dynamic_update_slice(codes, code_w, (0, w.lo))

            # ---- accounting: tile presence of the pre-policy state -------
            run = state == RUN
            alloc_p = jnp.sum(
                jnp.where(
                    run[..., None] & (pborn.astype(jnp.int32)[..., None] == ar_p),
                    dop[..., None], 0.0,
                ),
                axis=1,
            )
            presence = jnp.where(
                state >= RUN,
                dop * jnp.clip(jnp.minimum(fin, w.t1) - jnp.maximum(start, w.t0), 0.0, None),
                0.0,
            ).sum(axis=1)
            ov_p = jnp.clip(jnp.minimum(stall_end, w.t1) - w.t0, 0.0, None)
            realloc_r = jnp.sum(alloc_p * ov_p, axis=1)

        with jax.named_scope("policy"):
            # ---- policy pass ---------------------------------------------
            parw_i = w.parw.astype(jnp.int32)
            stall_rdy = stall_end[:, jnp.clip(parw_i, 0, P - 1)]
            adm = jnp.maximum(ready_t, stall_rdy)
            if pol == _CYC or (pol == _ADS and cfg.admission):
                adm = jnp.maximum(adm, w.ertw[None, :])
            can = (state == READY) & (adm <= w.t1 + 1e-12)
            own_freed = freed_t_p[:, jnp.clip(parw_i, 0, P - 1)]

            free_p = w.capsg[None, :] - alloc_p
            stalled_p = stall_end > w.t1

            d_lad = ladder_durations(w)

            cap_pool = jnp.broadcast_to(w.capsg, (R, P))
            if pol in (_CYC, _CYC_S):
                # runners keep their tiles until they finish: ready jobs bid
                # on *free* capacity only (under overload the planned slots
                # collide and instances queue exactly like the scalar)
                want = jnp.where(can, w.pdw[None, :], 0.0)
                grant = edf_alloc(
                    w, want, can, jnp.broadcast_to(w.parw[None, :], (R, W)),
                    w.pdw[:, None], free_p,
                )
                started = can & (grant > 0.5)
            else:
                with jax.named_scope("ads"):
                    # ---- ads Algorithm 2, mirrored in two phases --------------
                    # Phase A (fast path): ready jobs start on *free* tiles at
                    # their quota while running jobs hold their allocation —
                    # under pressure this yields the scalar engine's best-effort
                    # small starts (fit_quota degrades to the largest rung that
                    # fits free), which is what later makes them at-risk and
                    # drives the grow cascade.
                    pborn_i = pborn.astype(jnp.int32)
                    cmaxw = w.candw[:, -1]
                    slack_rdy = jnp.broadcast_to(w.tgtw[None, :], (R, W)) - jnp.maximum(adm, w.t0)
                    want_rdy = jnp.where(can, want_of(w, d_lad, rem, slack_rdy), 0.0)
                    partA = jnp.broadcast_to(w.parw[None, :], (R, W))
                    grantA = edf_alloc(w, want_rdy, can, partA, w.candw, free_p)
                    started1 = can & (grantA > 0.5)

                    # ChkTrigger on the post-fast-path state; the running set is
                    # the pre-start snapshot, as in the scalar policy.
                    alloc2 = alloc_p + per_part((started1, parw_i[None, :]), grantA)
                    free2 = cap_pool - alloc2
                    still = can & ~started1
                    own_free2 = free2[:, jnp.clip(parw_i, 0, P - 1)]
                    blocked = still & (want_rdy > own_free2 + 0.5)
                    # The scalar engine syncs ``job.progress`` only at the job's
                    # chunk boundaries (n_chunks per duration) and at realloc
                    # freezes, so its projection ``now + remaining`` runs on
                    # progress stale by up to one chunk interval — a job started
                    # with a thin margin drifts into at-risk between chunk
                    # syncs even though it is on track.  ``adv`` anchors the
                    # chunk grid (start / freeze end); the staleness at t1 is
                    # the time since the last chunk boundary before t1.
                    chunk_iv = jnp.maximum(d_cur, 1e-12) / jnp.float32(cfg.n_chunks)
                    stale_amt = jnp.where(
                        run,
                        jnp.mod(jnp.clip(w.t1 - adv, 0.0, None), chunk_iv),
                        0.0,
                    )
                    rem_stale = jnp.clip(
                        ((fin - w.t1) + stale_amt) / jnp.maximum(d_cur, 1e-12),
                        0.0, 1.0,
                    )
                    at_risk = run & (cmaxw[None, :] > dop + 0.5) & (
                        w.t1 + rem_stale * d_cur > tgtb
                    )
                    blocked_p = per_part((blocked, parw_i[None, :]))
                    risk_p = per_part((at_risk, pborn_i))
                    trig_p = (blocked_p | risk_p) & ~stalled_p
                    own_trig_run = own_of(trig_p, pborn_i, False)
                    own_trig_rdy = trig_p[:, jnp.clip(parw_i, 0, P - 1)]

                    # Phase B (quota control): triggered partitions re-bid
                    # running + still-ready jobs EDF against the full capacity,
                    # using the same stale-progress projection as the trigger.
                    want_run_q = want_of(w, d_lad, rem_stale, tgtb - w.t1)
                    entryB = (run & own_trig_run) | (still & own_trig_rdy)
                    wantB = jnp.where(run, jnp.maximum(want_run_q, 1.0), want_rdy)
                    grantB = edf_alloc(
                        w, wantB, entryB, jnp.where(run, pborn, partA), w.candw, cap_pool
                    )

                    # benefit/cost gates: grow only when the saved time beats the
                    # whole-partition stall it causes; shrink only to admit a
                    # blocked job; never preempt a runner to zero.
                    d_new = dur(w.workw, w.iow, w.syncw, grantB)
                    n_run_p = per_part((run, pborn_i), 1.0)
                    own_nrun = own_of(n_run_p, pborn_i, 1.0)
                    own_hops = w.hopsg[jnp.clip(pborn_i, 0, P - 1)]
                    stall_c = (
                        cfg.fixed_s + cfg.decision_s + own_hops * cfg.per_hop_s
                        + w.ckptw[None, :] * jnp.abs(grantB - dop) * cfg.inv_bw
                    )
                    benefit = rem_stale * (d_cur - d_new)
                    grow_ok = benefit > stall_c * jnp.maximum(own_nrun, 1.0) * cfg.realloc_gate
                    blocked_own = own_of(blocked_p, pborn_i, False)
                    g = grantB
                    g = jnp.where(g > dop, jnp.where(grow_ok, g, dop), g)
                    g = jnp.where((g < dop) & ~blocked_own, dop, g)
                    g = jnp.where(g < 0.5, dop, g)
                    g = jnp.where(run & own_trig_run, g, dop)

                    # Phase B starts: validate against free + net freed tiles,
                    # EDF order, dropping what no longer fits (scalar lines
                    # 209-219).
                    freed_p = per_part((run & own_trig_run, pborn_i),
                                       jnp.maximum(dop - g, 0.0))
                    grown_p = per_part((run & own_trig_run, pborn_i),
                                       jnp.maximum(g - dop, 0.0))
                    availB = free2 + freed_p - grown_p
                    dB = jnp.where(still & own_trig_rdy, grantB, 0.0)
                    dB_s = jnp.take(dB, w.permr, axis=1)
                    exclB, _, availg = _class_prefix(
                        cfg, jnp.take(partA, w.permr, axis=1), availB, dB_s.dtype
                    )
                    keep_s = (dB_s > 0) & (exclB(dB_s) + dB_s <= availg + 0.5)
                    started2 = jnp.take(keep_s, w.ipermr, axis=1)
                    started = started1 | started2
                    grant = jnp.where(
                    run, g, jnp.where(started1, grantA, jnp.where(started2, grantB, 0.0))
                )

        with jax.named_scope("apply"):
            # ---- apply: starts -------------------------------------------
            # a job admitted before this round opened was blocked on
            # capacity; it starts at the in-round release event, not at adm
            d_start = dur(w.workw, w.iow, w.syncw, grant)
            start_t = jnp.where(
                adm >= w.t0 - 1e-9,
                adm,
                jnp.minimum(jnp.maximum(own_freed, w.t0), w.t1),
            )
            state = jnp.where(started, RUN, state)
            start = jnp.where(started, start_t, start)
            fin = jnp.where(started, start_t + rem * d_start, fin)
            pborn = jnp.where(started, w.parw[None, :], pborn)
            subb = jnp.where(started, w.subw[None, :], subb)
            tgtb = jnp.where(started, w.tgtw[None, :], tgtb)

            # ---- apply: resizes (ads) -----------------------------------
            if pol == _ADS:
                resized = run & (jnp.abs(grant - dop) > 0.5)
                moved_j = jnp.where(
                    resized, w.ckptw[None, :] * jnp.abs(grant - dop), 0.0
                )
                ohres = pborn.astype(jnp.int32)[..., None] == ar_p
                moved_p = jnp.sum(
                    jnp.where(ohres, moved_j[..., None], 0.0), axis=1
                )
                changed_p = jnp.any(resized[..., None] & ohres, axis=1)
                stall_p = jnp.where(
                    changed_p,
                    cfg.fixed_s + cfg.decision_s + w.hopsg[None, :] * cfg.per_hop_s
                    + moved_p * cfg.inv_bw,
                    0.0,
                )
                stall_end = jnp.maximum(stall_end, w.t1 + stall_p)
                rem_now = jnp.clip((fin - w.t1) / jnp.maximum(d_cur, 1e-12), 0.0, 1.0)
                d_res = dur(w.workw, w.iow, w.syncw, grant)
                fin = jnp.where(resized, w.t1 + rem_now * d_res, fin)
                dop = jnp.where(resized, grant, dop)
                # whole-partition freeze: survivors wait out the stall
                stall_own = own_of(stall_p, pborn.astype(jnp.int32), 0.0)
                frozen = (state == RUN) & ~started & (stall_own > 0)
                fin = jnp.where(frozen, fin + stall_own, fin)
                # the freeze is where the scalar engine syncs progress: the
                # staleness clock restarts at the stall's end
                adv = jnp.where(frozen | resized, w.t1 + stall_own, adv)
                nre = nre + jnp.sum(changed_p.astype(jnp.float32), axis=1)
                rbytes = rbytes + jnp.sum(moved_p, axis=1)

            dop = jnp.where(started, grant, dop)
            adv = jnp.where(started, start_t, adv)

            # ---- accumulate tile-seconds into the segment buckets --------
            start_corr = jnp.sum(
                jnp.where(started, grant * jnp.clip(w.t1 - start_t, 0.0, None), 0.0),
                axis=1,
            )
            busy_r = jnp.clip(presence + start_corr - realloc_r, 0.0, None)
            onehot = (jnp.arange(S_) == w.sg).astype(busy.dtype)
            busy = busy + onehot[None, :] * busy_r[:, None]
            rel = rel + onehot[None, :] * realloc_r[:, None]

        with jax.named_scope("window"):
            # ---- pack the window back ------------------------------------
            st = pack(st, w.lo, (state, ready_t, deg, start, fin, dop, pborn,
                               rem, subb, tgtb, adv))
        return st, codes, stall_end, busy, rel, nre, rbytes, dwork

    # ---- tp_driven: a walk at each of the scalar engine's instants ---------
    # ``TpDrivenPolicy.on_point`` re-walks its partition's queue (EDF quota
    # pass, then the tile-saturating bump passes) at every queue change: a
    # job becoming ready, a finish, a drop and the end of a stall
    # (``resume``), and nowhere else; a stalled partition takes no walk.
    # The fixed point of quota + bump moves with the walk's instant (slack
    # shrinks while a runner's synced progress stands still), so walking
    # once at a round's end would resize where the scalar does not and
    # coalesce a round's walks into one.  A round runs its lanes' instants
    # in time order instead: each trip takes every lane's earliest pending
    # instant and walks the partitions that have one there.
    #
    # A round's instants per lane: a job becomes ready at most once and
    # finishes (or drops) at most once, and a partition resumes at most
    # once per stall, which lasts at least fixed_s + decision_s.
    round_s = float(np.max(np.asarray(const["t1"]) - np.asarray(const["t0"])))
    resumes = P * (1 + math.ceil(round_s / (cfg.fixed_s + cfg.decision_s)))
    tp_trips = (3 if cfg.drop_mode == 2 else 2) * W + resumes

    def code_of(state, fin, deg):
        """Finish codes of a window: ``t`` done at t, ``-t - 1`` dropped or
        degraded at t, ``inf`` unresolved."""
        return jnp.where(
            state >= DONE, jnp.where(deg > 0.5, -fin - 1.0, fin), jnp.inf
        )

    # A walk's decisions turn on differences of times: a runner's progress
    # is (finish - last sync) over its duration, a quota's slack is
    # sub-deadline - now, and a resize scales the error of either by its
    # ratio of durations.  float32 times near 1.5 s carry 1.2e-7 s of
    # rounding, enough to flip a near-tie now and then, after which the
    # drive leaves the scalar engine's trajectory.  So the walk runs on
    # times relative to its round's ``t0`` (a round's instants lie within
    # a millisecond of it, a runner's finish within a job's life), and
    # the finish, sync and stall-end times it carries from round to round
    # are kept as an absolute float32 plane ``hi`` and the remainder
    # ``lo`` that ``hi`` rounded away: ``hi - t0`` is exact wherever
    # ``hi`` lies within a factor of two of ``t0`` (Sterbenz), and tiny
    # where it does not.  Finish codes carry theirs (``codes_lo``, the
    # sensors' from the host in the second half of ``codes0``), since a
    # predecessor's finish is the instant its successor becomes ready.
    def decode(code):
        """The time of a finish code."""
        return jnp.where(code < 0, -code - 1.0, code)

    def rel_of(w, hi, lo):
        """Round-relative time of the pair (``hi``, ``lo``)."""
        return (hi - w.t0) + lo

    def split(w, x):
        """The pair (``hi``, ``lo``) of a round-relative time ``x``."""
        hi = w.t0 + x
        return hi, jnp.where(jnp.isfinite(hi), x - (hi - w.t0), 0.0)

    def tp_body(r, carry):
        """One round of tp_driven: walks at its queue-change instants."""
        (st, codes, stall_hi, busy, rel, nre, rbytes, dwork, walks, lo_st,
         stall_lo, codes_lo) = carry
        with jax.named_scope("window"):
            w = window(r, st)
            (state, ready_t, deg, start, fin, dop, pborn, rem, subb, timer,
             adv) = w.planes
            fin_lo, adv_lo = (lax.dynamic_slice(a, (0, w.lo), (R, W))
                              for a in lo_st)

        with jax.named_scope("step"):
            # from here to the round's end ``fin``, ``adv``, ``stall_end``
            # and every instant are relative to t0; ``ready_t``, ``start``,
            # ``subb`` and ``timer`` stay absolute
            fin = rel_of(w, fin, fin_lo)
            adv = rel_of(w, adv, adv_lo)
            stall_end = rel_of(w, stall_hi, stall_lo)
            state, fin, dop, rem, adv, stall_end, nre, rbytes = seam(
                w, state, fin, dop, rem, adv, pborn, stall_end, nre, rbytes,
                t0=jnp.float32(0.0),
            )
            # a hot-swap retargets every queued job's sub-deadline (preempted
            # ones too) and keeps a runner's: ``subb`` holds the one a job
            # keeps, inf where it follows its segment's binding
            swapped = const["entry"][w.r] & const["swap"][w.sg]
            subb = jnp.where(swapped & (state != RUN), jnp.inf, subb)
            # predecessors' finish times and drops as the round opens; those
            # inside the window are read again from the live planes at each
            # instant
            flat = w.predw.reshape(-1)
            pc0 = codes[:, flat].reshape(R, W, PM)
            rt0 = (decode(pc0) - w.t0) + codes_lo[:, flat].reshape(R, W, PM)
            dg0 = pc0 < -0.5
            loc = flat - w.lo
            inwin = ((loc >= 0) & (loc < W)).reshape(1, W, PM)
            loc = jnp.clip(loc, 0, W - 1)
            rel_w = w.relw[None, :] - w.t0
            t1_r = w.t1 - w.t0
            d_lad = ladder_durations(w)
            cap_pool = jnp.broadcast_to(w.capsg, (R, P))
            parw_i = jnp.broadcast_to(w.parw.astype(jnp.int32)[None, :], (R, W))

        def edf_lanes(want, entry, part, key):
            """``edf_alloc`` with the bump pass over each lane's own EDF
            order by ``key`` (ties by window position, as the round's).

            The permutation is applied as a one-hot select and sum over
            (R, W, W), not as a gather, which a TPU would run element by
            element; it runs only in the rounds after a hot-swap."""
            perm = jnp.argsort(key, axis=1, stable=True)
            oh = perm[:, :, None] == jnp.arange(W, dtype=perm.dtype)

            def take(a):
                if a.dtype == jnp.bool_:
                    return jnp.any(oh & a[:, None, :], axis=2)
                return jnp.sum(jnp.where(oh, a[:, None, :], 0), axis=2)

            with jax.named_scope("alloc"):
                entry_s, part_s = take(entry), take(part)
                cand_s = jnp.stack(
                    [take(jnp.broadcast_to(w.candw[:, k], (R, W)))
                     for k in range(C)], axis=-1)
                grant_s = _alloc_ladder(
                    cfg, take(want), entry_s, part_s, cand_s, cap_pool)
                grant_s = _bump_work_conserving(
                    cfg, grant_s, entry_s, part_s, cand_s, cap_pool)
                return jnp.sum(jnp.where(oh, grant_s[:, :, None], 0.0), axis=1)

        def alloc_of(state, pborn, dop):
            return per_part((state == RUN, pborn.astype(jnp.int32)), dop)

        def readiness(state, fin, deg):
            """Each pending job's ready time, whether its predecessors are
            all resolved, whether one of them was dropped or degraded, and
            whether the last of them is a job of the window (whose finish
            or drop walks the queue before the job's own ready event)."""
            done = state >= DONE

            def of_preds(a, a0):
                return jnp.where(
                    inwin, jnp.take(a, loc, axis=1).reshape(R, W, PM), a0)

            rt = of_preds(jnp.where(done, fin, jnp.inf), rt0)
            dg = of_preds(done & (deg > 0.5), dg0)
            res_t = jnp.maximum(rel_w, jnp.max(rt, axis=-1))
            resolvable = (state == PEND) & ~jnp.any(jnp.isinf(rt), axis=-1)
            by_job = jnp.any(inwin & (rt == res_t[..., None]), axis=-1)
            return res_t, resolvable, jnp.any(dg, axis=-1), by_job

        def instants(planes, stall_end, tcur, res_t, resolvable):
            """Each partition's next instant from ``tcur`` on (R, P)."""
            state, ready_t, _deg, _s, fin, _d, pborn = planes[:7]
            run = state == RUN
            # readiness is a walk only where the partition is not stalled
            # then; a job readied under a stall waits for the resume's walk
            own_se = _pick(stall_end[:, None, :], jnp.clip(parw_i, 0, P - 1))
            ev = jnp.where(
                run, fin,
                jnp.where(resolvable & (own_se <= res_t), res_t, jnp.inf),
            )
            if cfg.drop_mode == 2:
                timer = planes[F_TIMER] - w.t0
                ev = jnp.minimum(ev, jnp.where(
                    state == READY, jnp.maximum(timer, ready_t - w.t0),
                    jnp.where(run & (fin > timer + 1e-9), timer, jnp.inf),
                ))
            ev = jnp.maximum(ev, tcur[:, None])
            part = jnp.where(run, pborn.astype(jnp.int32), parw_i)
            ev_p = jnp.stack(
                [jnp.min(jnp.where(part == p, ev, jnp.inf), axis=1)
                 for p in range(P)],
                axis=1,
            )
            return jnp.minimum(
                ev_p, jnp.where(stall_end > tcur[:, None], stall_end, jnp.inf)
            )

        def more(c):
            return (c[0] < tp_trips) & jnp.any(jnp.min(c[-1], axis=1) <= t1_r)

        def trip(c):
            (i, planes, stall_end, tcur, busy_r, rel_r, nre, rbytes, dwork,
             walks, ev_p) = c
            (state, ready_t, deg, start, fin, dop, pborn, rem, subb, timer,
             adv) = planes
            tau_l = jnp.min(ev_p, axis=1)
            act = tau_l <= t1_r
            tau = jnp.where(act, jnp.maximum(tau_l, tcur), tcur)
            tau_c = tau[:, None]

            # tile-seconds over (tcur, tau]: a stalled partition's tiles
            # are reallocation waste
            alloc_p = alloc_of(state, pborn, dop)
            stl = stall_end > tcur[:, None]
            span = tau - tcur
            busy_r = busy_r + jnp.sum(jnp.where(stl, 0.0, alloc_p), axis=1) * span
            rel_r = rel_r + jnp.sum(jnp.where(stl, alloc_p, 0.0), axis=1) * span

            # ---- the queue changes at tau --------------------------------
            # finishes and drops first: their successors are ready at tau,
            # in the walk their queue change triggers
            state = jnp.where((state == RUN) & (fin <= tau_c), DONE, state)
            d_cur = dur(w.workw, w.iow, w.syncw, dop)
            if cfg.drop_mode == 2:
                # hard e2e-deadline dequeue at the armed timers (exact drop
                # times)
                timer_r = timer - w.t0
                drop_run = (state == RUN) & (timer_r <= tau_c) & (
                    fin > timer_r + 1e-9)
                droptime = jnp.where(
                    drop_run, timer_r, jnp.maximum(timer_r, ready_t - w.t0))
                dropping = drop_run | ((state == READY) & (droptime <= tau_c))
                rem_d = jnp.where(
                    drop_run,
                    jnp.clip((fin - droptime) / jnp.maximum(d_cur, 1e-12), 0.0, 1.0),
                    rem,
                )
                d_plan = dur(w.workw, w.iow, w.syncw, w.pdw[None, :])
                dwork = dwork + jnp.sum(
                    jnp.where(dropping, rem_d * d_plan * w.pdw[None, :], 0.0),
                    axis=1,
                )
                state = jnp.where(dropping, DROP, state)
                fin = jnp.where(dropping, droptime, fin)
                deg = jnp.where(dropping, 1.0, deg)
            res_t, resolvable, pdeg, by_job = readiness(state, fin, deg)
            newready = resolvable & (res_t <= tau_c)
            state = jnp.where(newready, READY, state)
            ready_t = jnp.where(newready, w.t0 + res_t, ready_t)
            deg = jnp.where(newready, pdeg, deg)

            # ---- the walk: partitions with an instant at tau, unstalled --
            walk_p = act[:, None] & (ev_p <= tau_c) & (stall_end <= tau_c)
            run = state == RUN
            rdy = state == READY
            pborn_i = pborn.astype(jnp.int32)
            part = jnp.where(run, pborn_i, parw_i)
            entry = (run | rdy) & _pick(
                walk_p[:, None, :], jnp.clip(part, 0, P - 1)
            )
            # a runner's progress is synced only where its partition last
            # stalled (``adv``): its quota runs on that progress
            rem_run = jnp.clip((fin - adv) / jnp.maximum(d_cur, 1e-12), 0.0, 1.0)
            sub_q = jnp.where(jnp.isfinite(subb), subb, w.subw[None, :])
            want = want_of(w, d_lad, jnp.where(run, rem_run, rem),
                           (sub_q - w.t0) - tau_c)
            # the round's EDF order sorts by the segment's sub-deadlines; a
            # job that keeps an earlier segment's (started, or preempted,
            # before a hot-swap) takes its lanes to an order of their own
            grant = lax.cond(
                jnp.any(entry & (sub_q != w.subw[None, :])),
                edf_lanes,
                lambda want, entry, part, _key: edf_alloc(
                    w, want, entry, part, w.candw, cap_pool, bump=True),
                want, entry, part, sub_q,
            )

            # ---- its resizes, preemptions and starts, at tau -------------
            started = entry & rdy & (grant > 0.5)
            resized = entry & run & (jnp.abs(grant - dop) > 0.5)
            preempt = resized & (grant < 0.5)
            moved_p = per_part((resized, pborn_i), w.ckptw[None, :] * jnp.where(
                preempt, dop, jnp.abs(grant - dop)))
            changed_p = per_part((resized, pborn_i))
            stall_p = jnp.where(
                changed_p,
                cfg.fixed_s + cfg.decision_s + w.hopsg[None, :] * cfg.per_hop_s
                + moved_p * cfg.inv_bw,
                0.0,
            )
            stall_end = jnp.where(changed_p, tau_c + stall_p, stall_end)
            # the whole partition freezes for its stall: runners resume at
            # its end (the resized at their new size) with their progress
            # synced there
            stall_run = own_of(stall_p, pborn_i, 0.0)
            frozen = run & (stall_run > 0)
            rem_now = jnp.clip((fin - tau_c) / jnp.maximum(d_cur, 1e-12), 0.0, 1.0)
            fin = jnp.where(
                resized,
                tau_c + stall_run + rem_now * dur(w.workw, w.iow, w.syncw, grant),
                jnp.where(frozen, fin + stall_run, fin),
            )
            adv = jnp.where(frozen, tau_c + stall_run, adv)
            dop = jnp.where(resized, grant, dop)
            # a grant of 0 preempts back to the ready queue
            rem = jnp.where(preempt, rem_now, rem)
            state = jnp.where(preempt, READY, state)
            fin = jnp.where(preempt, jnp.inf, fin)
            # a start in a partition this walk stalled begins at its end
            begin = tau_c + own_of(stall_p, parw_i, 0.0)
            state = jnp.where(started, RUN, state)
            start = jnp.where(started, w.t0 + tau_c, start)
            fin = jnp.where(
                started, begin + rem * dur(w.workw, w.iow, w.syncw, grant), fin
            )
            dop = jnp.where(started, grant, dop)
            pborn = jnp.where(started, w.parw[None, :], pborn)
            subb = jnp.where(started, sub_q, subb)
            adv = jnp.where(started, begin, adv)
            if cfg.drop_mode == 2:
                # the scalar engine arms a job's e2e timer when its ready
                # event finds it still queued: a job started by the walk of
                # the finish or drop that readied it arms none, and may run
                # late undropped
                timer = jnp.where(newready, w.e2ew[None, :], timer)
                timer = jnp.where(started & newready & by_job, jnp.inf, timer)
            nre = nre + jnp.sum(changed_p.astype(jnp.float32), axis=1)
            rbytes = rbytes + jnp.sum(moved_p, axis=1)
            walks = walks + jnp.sum(walk_p.astype(jnp.float32), axis=1)

            planes = (state, ready_t, deg, start, fin, dop, pborn, rem, subb,
                      timer, adv)
            return (i + 1, planes, stall_end, tau, busy_r, rel_r, nre, rbytes,
                    dwork, walks,
                    instants(planes, stall_end, tau, res_t, resolvable & ~newready))

        with jax.named_scope("policy"), jax.named_scope("tp"), \
                jax.named_scope("walk"):
            planes = (state, ready_t, deg, start, fin, dop, pborn, rem, subb,
                      timer, adv)
            tcur = jnp.zeros((R,), dtype=jnp.float32)
            zr = jnp.zeros((R,), dtype=jnp.float32)
            (_, planes, stall_end, tcur, busy_r, rel_r, nre, rbytes, dwork,
             walks, _) = lax.while_loop(more, trip, (
                0, planes, stall_end, tcur, zr, zr, nre, rbytes, dwork, walks,
                instants(planes, stall_end, tcur,
                         *readiness(state, fin, deg)[:2]),
            ))
            (state, ready_t, deg, start, fin, dop, pborn, rem, subb, timer,
             adv) = planes

        with jax.named_scope("apply"):
            # the rest of the round holds no instant
            alloc_p = alloc_of(state, pborn, dop)
            stl = stall_end > tcur[:, None]
            span = t1_r - tcur
            busy_r = busy_r + jnp.sum(jnp.where(stl, 0.0, alloc_p), axis=1) * span
            rel_r = rel_r + jnp.sum(jnp.where(stl, alloc_p, 0.0), axis=1) * span
            fin, fin_lo = split(w, fin)
            adv, adv_lo = split(w, adv)
            stall_hi, stall_lo = split(w, stall_end)
            code = code_of(state, fin, deg)
            codes = lax.dynamic_update_slice(codes, code, (0, w.lo))
            codes_lo = lax.dynamic_update_slice(codes_lo, jnp.where(
                jnp.isfinite(code), rel_of(w, fin, fin_lo) - (decode(code) - w.t0),
                0.0), (0, w.lo))
            onehot = (jnp.arange(S_) == w.sg).astype(busy.dtype)
            busy = busy + onehot[None, :] * busy_r[:, None]
            rel = rel + onehot[None, :] * rel_r[:, None]

        with jax.named_scope("window"):
            st = pack(st, w.lo, (state, ready_t, deg, start, fin, dop, pborn,
                                 rem, subb, timer, adv))
            lo_st = pack(lo_st, w.lo, (fin_lo, adv_lo))
        return (st, codes, stall_hi, busy, rel, nre, rbytes, dwork, walks,
                lo_st, stall_lo, codes_lo)

    step = tp_body if pol == _TP else body

    def loop(*carry):
        return lax.fori_loop(0, n_rounds, step, carry)

    loop.body = step  # exposed for eager single-round debugging/tests
    return loop


# ---------------------------------------------------------------------------
# entry point + compile cache
# ---------------------------------------------------------------------------
_LOOP_CACHE: Dict[Tuple, object] = {}


def clear_kernel_cache() -> None:
    """Drop compiled round loops (test isolation hook)."""
    _LOOP_CACHE.clear()


def _const_digest(const_np: Dict[str, np.ndarray]) -> bytes:
    """Content identity of the host-precomputed statics.

    The compiled loop closes over the ``const`` arrays as baked-in
    compile-time constants, so the cache key must distinguish cells by
    *value*, not just shape: two portfolios (different caps / deadline
    bindings / staging volumes) over the same skeleton share every
    shape yet need different compiled loops.
    """
    h = hashlib.sha1()
    for k in sorted(const_np):
        v = np.ascontiguousarray(const_np[k])
        h.update(k.encode())
        h.update(str(v.dtype).encode())
        h.update(str(v.shape).encode())
        h.update(v.tobytes())
    return h.digest()


def round_loop(cfg: KernelConfig, const_np: Dict[str, np.ndarray]):
    """The jitted round loop of one problem: ``(work, io, codes0)`` ->
    the final ``(state planes, codes, stall_end, busy, realloc,
    n_realloc, realloc_bytes, dropped_work)``.  tp_driven's ``codes0``
    holds after the codes what float32 rounded away from each code's
    time (twice the width), and its loop returns after the rest the
    walks each lane took and the remainders of its finish and sync
    planes, stall ends and finish codes.

    ``const_np`` holds the host-precomputed statics (see
    :func:`repro.core.sim.soa.build_problem`), closed over as
    compile-time constants; the lane shapes come from the arguments.
    Nothing is traced before the first call or ``.lower(...)``, so the
    loop can be compiled ahead of time for a device that is described
    rather than attached.
    """
    const = {k: jnp.asarray(v) for k, v in const_np.items()}
    S_ = int(const["caps"].shape[0])
    P = cfg.P

    @jax.jit
    def run(work, io, codes0):
        R, N = work.shape
        cdev = dict(const)
        cdev["work"] = work
        cdev["io"] = io
        loop = _build_loop(cfg, cdev)
        zeros = jnp.zeros((R, N), dtype=jnp.float32)
        inf = jnp.full((R, N), jnp.inf, dtype=jnp.float32)
        fills = {
            F_FIN: inf, F_SUB: inf, F_TGT: inf,
            F_PART: jnp.full((R, N), -1.0, dtype=jnp.float32),
            F_REM: jnp.ones((R, N), dtype=jnp.float32),
        }
        st0 = tuple(fills.get(f, zeros) for f in range(NFIELDS))
        zf = partial(jnp.zeros, dtype=jnp.float32)
        # tp_driven: its walks, and the remainders of its finish and sync
        # planes, stall ends and finish codes (see ``rel_of``)
        tp = ()
        if cfg.policy == _TP:
            A1 = codes0.shape[1] // 2
            codes0, codes0_lo = codes0[:, :A1], codes0[:, A1:]
            tp = (zf((R,)), (zeros, zeros), zf((R, P)), codes0_lo)
        return loop(
            st0, codes0, zf((R, P)), zf((R, S_)), zf((R, S_)),
            zf((R,)), zf((R,)), zf((R,)), *tp,
        )

    return run


def simulate(
    cfg: KernelConfig,
    const_np: Dict[str, np.ndarray],
    lanes_np: Dict[str, np.ndarray],
) -> Dict[str, np.ndarray]:
    """Run the compiled round loop; returns final state as NumPy arrays.

    ``lanes_np`` holds the per-lane trace data (``work``, ``io``,
    ``codes0``).  The compiled loop (:func:`round_loop`) is cached on
    ``(cfg, const-content digest, lane shapes)`` — the const arrays are
    closed over as compile-time constants, so the key must carry their
    *values* (see :func:`_const_digest`); re-running the same scenario
    cell with new seeds skips compilation entirely.
    """
    R, N = lanes_np["work"].shape
    key = (
        cfg,
        _const_digest(const_np),
        (R, N, lanes_np["codes0"].shape[1]),
    )
    loop = _LOOP_CACHE.get(key)
    if loop is None:
        metrics.count("soa_loop_builds")
        loop = _LOOP_CACHE[key] = round_loop(cfg, const_np)
    n_rounds = int(const_np["t0"].shape[0])
    metrics.count("soa_rounds", n_rounds)
    metrics.count("soa_lanes", R)
    metrics.count("soa_lane_rounds", n_rounds * R)

    # while the registry is on, each phase waits for its own result (which
    # the next phase would wait for anyway), so its span is its own cost
    with metrics.phase("soa_upload"):
        lanes = tuple(
            jnp.asarray(lanes_np[k]) for k in ("work", "io", "codes0")
        )
        if metrics.enabled():
            jax.block_until_ready(lanes)
    with metrics.phase("soa_loop"):
        out = loop(*lanes)
        if metrics.enabled():
            jax.block_until_ready(out)
    with metrics.phase("soa_fetch"):
        st, codes, stall_end, busy, rel, nre, rbytes, dwork, *tp = out
        walks = tp[:1]
        res = {
            "state": np.asarray(st[F_STATE]),
            "ready_t": np.asarray(st[F_READY]),
            "deg": np.asarray(st[F_DEG]),
            "start": np.asarray(st[F_START]),
            "fin": np.asarray(st[F_FIN]),
            "dop": np.asarray(st[F_DOP]),
            "codes": np.asarray(codes),
            "busy": np.asarray(busy, dtype=np.float64),
            "realloc": np.asarray(rel, dtype=np.float64),
            "n_realloc": np.asarray(nre, dtype=np.float64),
            "realloc_bytes": np.asarray(rbytes, dtype=np.float64),
            "dropped_work": np.asarray(dwork, dtype=np.float64),
        }
        if walks:
            res["tp_walks"] = np.asarray(walks[0], dtype=np.float64)
    metrics.count("soa_resizes", int(res["n_realloc"].sum()))
    if walks:
        metrics.count("soa_tp_walks", int(res["tp_walks"].sum()))
    return res
