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
* policy decisions (cyc / cyc_s / tp_driven / ads_tile) re-expressed as
  masked ladder/EDF array ops (see ``_alloc_ladder``), with the
  engine's quota semantics: ``grant = largest candidate <=
  min(want, tiles_left)`` where ``want`` is the smallest candidate
  meeting the deadline (``fit_quota`` equivalence);
* schedule hot-swaps as a ``lax.cond`` seam step (capacity switch,
  vectorized largest-first preemption, staging bytes precomputed on the
  host).

Everything is float32; the absolute times in a <=2 s horizon keep
~1e-7 s resolution, far below the multi-ms effects under study.  The
contract with the scalar engine is **distributional** (KS + CI overlap
+ exact structural invariants), enforced by
``benchmarks.check_equivalence --mode distributional`` — see
``docs/performance.md#soa-backend`` for what is and is not guaranteed.
"""
from __future__ import annotations

import dataclasses
import hashlib
from functools import partial
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
    F_TGT,     # ads slack-shared target bound at start
    F_ADV,     # last progress-sync time (start / freeze / stall end): the
               # scalar engine only advances ``job.progress`` at realloc
               # freezes, so its at-risk and quota projections run on
               # progress *stale since this time* — reproduced here
) = range(11)
NFIELDS = 11

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
    queue entries (EDF order) to their next candidate rung.  Two
    conservative passes approximate the scalar ``while bumped`` loop
    (each pass assumes every earlier eligible entry takes its bump, so
    it never over-commits)."""
    excl, total, capg = _class_prefix(cfg, part_s, cap_p, grant.dtype)

    def one_pass(grant):
        above = cand_s > grant[..., None] + 0.5
        nxt = jnp.min(jnp.where(above, cand_s, jnp.inf), axis=-1)
        delta = jnp.where(entry & jnp.isfinite(nxt), nxt - grant, 0.0)
        leftg = capg - total(grant)
        # the scalar walk skips an entry whose bump no longer fits and
        # still offers the tiles to later entries; a plain prefix gate
        # would block them, so relax the take-set to that fixed point
        take = delta > 0
        for _ in range(3):
            cume = excl(jnp.where(take, delta, 0.0))
            take = (delta > 0) & (cume + delta <= leftg + 0.5)
        # enforce feasibility of the final set (prefix over taken only)
        cume = excl(jnp.where(take, delta, 0.0))
        ok = take & (cume + delta <= leftg + 0.5)
        return jnp.where(ok, grant + delta, grant)

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

    def body(r, carry):
        st, codes, stall_end, busy, rel, nre, rbytes, dwork = carry
        with jax.named_scope("window"):
            t0 = const["t0"][r]
            t1 = const["t1"][r]
            sg = const["seg"][r]
            lo = const["lo"][r]

            # ``st`` is a tuple of NFIELDS separate (R, N) planes: updating
            # a (R, W) window of each is in-place under the fori_loop,
            # whereas a packed (R, N, NFIELDS) array made XLA:CPU copy the
            # whole state every round (~7x the slice cost)
            (state, ready_t, deg, start, fin, dop, pborn, rem, subb, tgtb,
             adv) = (
                lax.dynamic_slice(a, (0, lo), (R, W)) for a in st
            )

            relw = lax.dynamic_slice(const["release"], (lo,), (W,))
            e2ew = lax.dynamic_slice(const["e2e"], (lo,), (W,))
            syncw = lax.dynamic_slice(const["sync"], (lo,), (W,))
            ckptw = lax.dynamic_slice(const["ckpt"], (lo,), (W,))
            predw = lax.dynamic_slice(const["preds"], (lo, 0), (W, PM))
            workw = lax.dynamic_slice(const["work"], (0, lo), (R, W))
            iow = lax.dynamic_slice(const["io"], (0, lo), (R, W))
            ertw = lax.dynamic_slice(const["ert"], (sg, lo), (1, W))[0]
            subw = lax.dynamic_slice(const["sub"], (sg, lo), (1, W))[0]
            tgtw = lax.dynamic_slice(const["tgt"], (sg, lo), (1, W))[0]
            pdw = lax.dynamic_slice(const["pdop"], (sg, lo), (1, W))[0]
            parw = lax.dynamic_slice(const["part"], (sg, lo), (1, W))[0]
            candw = lax.dynamic_slice(const["cands"], (sg, lo, 0), (1, W, C))[0]
            capsg = lax.dynamic_slice(const["caps"], (sg, 0), (1, P))[0]
            hopsg = lax.dynamic_slice(const["hops"], (sg, 0), (1, P))[0]
            stagedg = lax.dynamic_slice(const["staged"], (sg, 0), (1, P))[0]
            permr = const["perm"][r]
            ipermr = const["iperm"][r]

            d_cur = dur(workw, iow, syncw, dop)

        with jax.named_scope("step"):
            # ---- seam hot-swap (rare; only at segment-entry rounds) ------
            do_swap = const["entry"][r] & const["swap"][sg]
            state, fin, dop, rem, adv, stall_end, nre, rbytes = lax.cond(
                do_swap,
                seam_step,
                lambda op: (op[0], op[1], op[2], op[3], op[4], op[6], op[7], op[8]),
                (state, fin, dop, rem, adv, pborn, stall_end, nre, rbytes,
                 t0, workw, iow, syncw, ckptw, capsg, hopsg, stagedg),
            )
            d_cur = dur(workw, iow, syncw, dop)

            # ---- finishes ------------------------------------------------
            # drop_mode 1: cyc's unconditional budget enforcement at the
            # bound sub-deadline; drop_mode 2: hard e2e-deadline dequeue;
            # drop_mode 0 (the runner's soft default): late jobs finish late
            run = state == RUN
            if cfg.drop_mode == 1:
                lim_run = subb
            elif cfg.drop_mode == 2:
                lim_run = jnp.broadcast_to(e2ew[None, :], (R, W))
            else:
                lim_run = jnp.full((R, W), jnp.inf, dtype=jnp.float32)
            drop_run = run & (lim_run <= t1) & (fin > lim_run + 1e-9)
            done_now = run & (fin <= t1) & ~drop_run
            state = jnp.where(done_now, DONE, state)

            # ---- readiness (release passed + all predecessors resolved) --
            pend = state == PEND
            pcodes = codes[:, predw.reshape(-1)].reshape(R, W, PM)
            unresolved = jnp.any(jnp.isinf(pcodes), axis=-1)
            rtimes = jnp.where(pcodes < 0, -pcodes - 1.0, pcodes)
            res_t = jnp.maximum(relw[None, :], jnp.max(rtimes, axis=-1))
            newready = pend & (relw[None, :] <= t1) & ~unresolved
            state = jnp.where(newready, READY, state)
            ready_t = jnp.where(newready, res_t, ready_t)
            deg = jnp.where(newready, jnp.any(pcodes < -0.5, axis=-1), deg)

            # ---- deadline drops (exact drop times, backdated) ------------
            if cfg.drop_mode == 1:
                lim_rdy = jnp.broadcast_to(subw[None, :], (R, W))
            elif cfg.drop_mode == 2:
                lim_rdy = jnp.broadcast_to(e2ew[None, :], (R, W))
            else:
                lim_rdy = jnp.full((R, W), jnp.inf, dtype=jnp.float32)
            rdy = state == READY
            drop_rdy = rdy & (lim_rdy <= t1)
            droptime = jnp.where(
                drop_run, lim_run, jnp.maximum(lim_rdy, ready_t)
            )
            dropping = drop_run | drop_rdy
            rem_d = jnp.where(
                drop_run,
                jnp.clip((fin - droptime) / jnp.maximum(d_cur, 1e-12), 0.0, 1.0),
                rem,
            )
            d_plan = dur(workw, iow, syncw, pdw[None, :])
            dwork = dwork + jnp.sum(
                jnp.where(dropping, rem_d * d_plan * pdw[None, :], 0.0), axis=1
            )
            state = jnp.where(dropping, DROP, state)
            fin = jnp.where(dropping, droptime, fin)
            deg = jnp.where(dropping, 1.0, deg)

            # in-round capacity-release times per partition: a job that sat
            # queued through earlier rounds can only start at the event that
            # made room (a completion or drop), never back at its admission
            # time — the scalar starts it from that event's callback
            fpart = jnp.where(drop_rdy, parw[None, :], pborn).astype(jnp.int32)
            freeing = done_now | dropping
            ar_p = jnp.arange(P, dtype=jnp.int32)
            freed_t_p = jnp.max(
                jnp.where(
                    freeing[..., None] & (fpart[..., None] == ar_p),
                    fin[..., None], t0,
                ),
                axis=1,
            )

            # ---- finish codes (idempotent re-derivation for the window) --
            terminal = state >= DONE
            code_w = jnp.where(
                terminal, jnp.where(deg > 0.5, -fin - 1.0, fin), jnp.inf
            )
            codes = lax.dynamic_update_slice(codes, code_w, (0, lo))

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
                dop * jnp.clip(jnp.minimum(fin, t1) - jnp.maximum(start, t0), 0.0, None),
                0.0,
            ).sum(axis=1)
            ov_p = jnp.clip(jnp.minimum(stall_end, t1) - t0, 0.0, None)
            realloc_r = jnp.sum(alloc_p * ov_p, axis=1)

        with jax.named_scope("policy"):
            # ---- policy pass ---------------------------------------------
            parw_i = parw.astype(jnp.int32)
            stall_rdy = stall_end[:, jnp.clip(parw_i, 0, P - 1)]
            adm = jnp.maximum(ready_t, stall_rdy)
            if pol == _CYC or (pol == _ADS and cfg.admission):
                adm = jnp.maximum(adm, ertw[None, :])
            can = (state == READY) & (adm <= t1 + 1e-12)
            own_freed = freed_t_p[:, jnp.clip(parw_i, 0, P - 1)]

            free_p = capsg[None, :] - alloc_p
            stalled_p = stall_end > t1

            d_lad = (
                workw[..., None] / (jnp.maximum(candw, 1.0)[None, :, :] * tf)
                + iow[..., None]
                + syncw[None, :, None] * jnp.maximum(candw - 1.0, 0.0)[None, :, :]
            )

            def want_of(rem_f, slack):
                """fit_quota's ladder target with no tile cap (cap folds in
                at grant time): smallest candidate meeting the deadline,
                else the largest rung."""
                if not cfg.quota_control:
                    return jnp.broadcast_to(candw[None, :, -1], (R, W))
                meet = rem_f[..., None] * d_lad <= slack[..., None] + 1e-12
                first = jnp.argmax(meet, axis=-1)
                anym = jnp.any(meet, axis=-1)
                picked = _pick(candw[None], first)
                return jnp.where(anym, picked, candw[None, :, -1])

            def edf_alloc(want_m, entry_m, part_m, cand_rows, pool, bump=False):
                """EDF-permute, ladder-allocate, inverse-permute."""
                with jax.named_scope("alloc"):
                    want_s = jnp.take(want_m, permr, axis=1)
                    entry_s = jnp.take(entry_m, permr, axis=1)
                    part_s = jnp.take(part_m, permr, axis=1)
                    cand_s = (
                        jnp.take(cand_rows, permr, axis=0)
                        if cand_rows.ndim == 2
                        else cand_rows
                    )
                    grant_s = _alloc_ladder(cfg, want_s, entry_s, part_s, cand_s, pool)
                    if bump:
                        grant_s = _bump_work_conserving(
                            cfg, grant_s, entry_s, part_s, cand_s, pool
                        )
                    return jnp.take(grant_s, ipermr, axis=1)

            def per_part(mask, val=None):
                """(R, P) per-partition sum (or any) keyed by an id array."""
                m, ids = mask
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

            cap_pool = jnp.broadcast_to(capsg, (R, P))
            if pol in (_CYC, _CYC_S):
                # runners keep their tiles until they finish: ready jobs bid
                # on *free* capacity only (under overload the planned slots
                # collide and instances queue exactly like the scalar)
                want = jnp.where(can, pdw[None, :], 0.0)
                grant = edf_alloc(
                    want, can, jnp.broadcast_to(parw[None, :], (R, W)),
                    pdw[:, None], free_p,
                )
                started = can & (grant > 0.5)
            elif pol == _TP:
                # tp re-walks ready+running EDF against the *full* capacity
                # on every queue change; between rounds the fixed point of
                # quota+bump is stationary, so recomputing it each round
                # reproduces the event-driven walk as long as the allocator
                # reaches the same fixed point (alloc_iters / bump_passes)
                slack_rdy = jnp.broadcast_to(subw[None, :], (R, W)) - jnp.maximum(adm, t0)
                want_rdy = jnp.where(can, want_of(rem, slack_rdy), 0.0)
                rem_run = jnp.clip(
                    (fin - t1) / jnp.maximum(d_cur, 1e-12), 0.0, 1.0
                )
                want_run_q = want_of(rem_run, subb - t1)
                own_stalled = own_of(
                    stalled_p, pborn.astype(jnp.int32), True
                )
                want_run = jnp.where(own_stalled, dop, want_run_q)
                want = jnp.where(run, want_run, want_rdy)
                grant = edf_alloc(
                    want, can | run, jnp.where(run, pborn, parw[None, :]),
                    candw, cap_pool, bump=True,
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
                    cmaxw = candw[:, -1]
                    slack_rdy = jnp.broadcast_to(tgtw[None, :], (R, W)) - jnp.maximum(adm, t0)
                    want_rdy = jnp.where(can, want_of(rem, slack_rdy), 0.0)
                    partA = jnp.broadcast_to(parw[None, :], (R, W))
                    grantA = edf_alloc(want_rdy, can, partA, candw, free_p)
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
                        jnp.mod(jnp.clip(t1 - adv, 0.0, None), chunk_iv),
                        0.0,
                    )
                    rem_stale = jnp.clip(
                        ((fin - t1) + stale_amt) / jnp.maximum(d_cur, 1e-12),
                        0.0, 1.0,
                    )
                    at_risk = run & (cmaxw[None, :] > dop + 0.5) & (
                        t1 + rem_stale * d_cur > tgtb
                    )
                    blocked_p = per_part((blocked, parw_i[None, :]))
                    risk_p = per_part((at_risk, pborn_i))
                    trig_p = (blocked_p | risk_p) & ~stalled_p
                    own_trig_run = own_of(trig_p, pborn_i, False)
                    own_trig_rdy = trig_p[:, jnp.clip(parw_i, 0, P - 1)]

                    # Phase B (quota control): triggered partitions re-bid
                    # running + still-ready jobs EDF against the full capacity,
                    # using the same stale-progress projection as the trigger.
                    want_run_q = want_of(rem_stale, tgtb - t1)
                    entryB = (run & own_trig_run) | (still & own_trig_rdy)
                    wantB = jnp.where(run, jnp.maximum(want_run_q, 1.0), want_rdy)
                    grantB = edf_alloc(
                        wantB, entryB, jnp.where(run, pborn, partA), candw, cap_pool
                    )

                    # benefit/cost gates: grow only when the saved time beats the
                    # whole-partition stall it causes; shrink only to admit a
                    # blocked job; never preempt a runner to zero.
                    d_new = dur(workw, iow, syncw, grantB)
                    n_run_p = per_part((run, pborn_i), 1.0)
                    own_nrun = own_of(n_run_p, pborn_i, 1.0)
                    own_hops = hopsg[jnp.clip(pborn_i, 0, P - 1)]
                    stall_c = (
                        cfg.fixed_s + cfg.decision_s + own_hops * cfg.per_hop_s
                        + ckptw[None, :] * jnp.abs(grantB - dop) * cfg.inv_bw
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
                    dB_s = jnp.take(dB, permr, axis=1)
                    exclB, _, availg = _class_prefix(
                        cfg, jnp.take(partA, permr, axis=1), availB, dB_s.dtype
                    )
                    keep_s = (dB_s > 0) & (exclB(dB_s) + dB_s <= availg + 0.5)
                    started2 = jnp.take(keep_s, ipermr, axis=1)
                    started = started1 | started2
                    grant = jnp.where(
                    run, g, jnp.where(started1, grantA, jnp.where(started2, grantB, 0.0))
                )

        with jax.named_scope("apply"):
            # ---- apply: starts -------------------------------------------
            # a job admitted before this round opened was blocked on
            # capacity; it starts at the in-round release event, not at adm
            d_start = dur(workw, iow, syncw, grant)
            start_t = jnp.where(
                adm >= t0 - 1e-9,
                adm,
                jnp.minimum(jnp.maximum(own_freed, t0), t1),
            )
            state = jnp.where(started, RUN, state)
            start = jnp.where(started, start_t, start)
            fin = jnp.where(started, start_t + rem * d_start, fin)
            pborn = jnp.where(started, parw[None, :], pborn)
            subb = jnp.where(started, subw[None, :], subb)
            tgtb = jnp.where(started, tgtw[None, :], tgtb)

            # ---- apply: resizes / preempts (tp, ads) ---------------------
            if pol in (_TP, _ADS):
                resized = run & (jnp.abs(grant - dop) > 0.5)
                if pol == _TP:
                    preempt = resized & (grant < 0.5)
                else:
                    preempt = jnp.zeros_like(resized)
                moved_j = jnp.where(
                    resized,
                    ckptw[None, :] * jnp.where(preempt, dop, jnp.abs(grant - dop)),
                    0.0,
                )
                ohres = pborn.astype(jnp.int32)[..., None] == ar_p
                moved_p = jnp.sum(
                    jnp.where(ohres, moved_j[..., None], 0.0), axis=1
                )
                changed_p = jnp.any(resized[..., None] & ohres, axis=1)
                stall_p = jnp.where(
                    changed_p,
                    cfg.fixed_s + cfg.decision_s + hopsg[None, :] * cfg.per_hop_s
                    + moved_p * cfg.inv_bw,
                    0.0,
                )
                stall_end = jnp.maximum(stall_end, t1 + stall_p)
                rem_now = jnp.clip((fin - t1) / jnp.maximum(d_cur, 1e-12), 0.0, 1.0)
                d_res = dur(workw, iow, syncw, grant)
                fin = jnp.where(resized & ~preempt, t1 + rem_now * d_res, fin)
                dop = jnp.where(resized & ~preempt, grant, dop)
                rem = jnp.where(preempt, rem_now, rem)
                state = jnp.where(preempt, READY, state)
                dop = jnp.where(preempt, 0.0, dop)
                fin = jnp.where(preempt, jnp.inf, fin)
                # whole-partition freeze: survivors wait out the stall
                stall_own = own_of(stall_p, pborn.astype(jnp.int32), 0.0)
                frozen = (state == RUN) & ~started & (stall_own > 0)
                fin = jnp.where(frozen, fin + stall_own, fin)
                # the freeze is where the scalar engine syncs progress: the
                # staleness clock restarts at the stall's end
                adv = jnp.where(
                    frozen | (resized & ~preempt), t1 + stall_own, adv
                )
                nre = nre + jnp.sum(changed_p.astype(jnp.float32), axis=1)
                rbytes = rbytes + jnp.sum(moved_p, axis=1)

            dop = jnp.where(started, grant, dop)
            adv = jnp.where(started, start_t, adv)

            # ---- accumulate tile-seconds into the segment buckets --------
            start_corr = jnp.sum(
                jnp.where(started, grant * jnp.clip(t1 - start_t, 0.0, None), 0.0),
                axis=1,
            )
            busy_r = jnp.clip(presence + start_corr - realloc_r, 0.0, None)
            onehot = (jnp.arange(S_) == sg).astype(busy.dtype)
            busy = busy + onehot[None, :] * busy_r[:, None]
            rel = rel + onehot[None, :] * realloc_r[:, None]

        with jax.named_scope("window"):
            # ---- pack the window back ------------------------------------
            new_w = (state, ready_t, deg, start, fin, dop, pborn, rem, subb,
                     tgtb, adv)
            st = tuple(
                lax.dynamic_update_slice(a, w, (0, lo))
                for a, w in zip(st, new_w)
            )
        return st, codes, stall_end, busy, rel, nre, rbytes, dwork

    def loop(st, codes, stall_end, busy, rel, nre, rbytes, dwork):
        return lax.fori_loop(
            0, n_rounds, body,
            (st, codes, stall_end, busy, rel, nre, rbytes, dwork),
        )

    loop.body = body  # exposed for eager single-round debugging/tests
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
    n_realloc, realloc_bytes, dropped_work)``.

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
        return loop(
            st0, codes0, zf((R, P)), zf((R, S_)), zf((R, S_)),
            zf((R,)), zf((R,)), zf((R,)),
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
    metrics.count("soa_rounds", int(const_np["t0"].shape[0]))
    metrics.count("soa_lanes", R)

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
        st, codes, stall_end, busy, rel, nre, rbytes, dwork = out
        return {
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
