"""Structure-of-arrays Monte-Carlo backend: host orchestration.

This module turns one scenario cell — (workflow, scenario, schedule
portfolio, policy, horizon) — into a *SoA problem*: the set of
lane-independent arrays that :mod:`repro.core.sim.soa_kernels` advances
for **R seeds simultaneously**.  The division of labour:

* **host (here, NumPy)** — job ordering (release-sorted), dependency
  columns into the finish-code array, the discrete round grid
  (seam-aligned, ``SoaOptions.dt_s`` cadence), per-round active job
  windows, per-round EDF permutations, per-segment schedule bindings
  (ERT / sub-deadline / slack-shared target / planned DoP / partition /
  DoP-candidate ladders), hot-swap capacities and staging volumes, and
  — after the kernel returns — assembly of one
  :class:`~repro.core.sim.engine.SimReport` per lane;
* **device (jax)** — everything per-lane: readiness, drops, policy
  quota/EDF decisions, reallocation stalls, tile-second accounting.

Fidelity contract (enforced by ``benchmarks.check_equivalence --mode
distributional`` and ``tests/test_soa.py``): the scalar engine remains
the semantics oracle; this backend reproduces it **distributionally**
(KS on chain-latency distributions, CI agreement on violation rate /
realloc waste / tiles reserved) and **exactly** on structural
invariants (job counts, seam times/spans, chain universe).  The known
approximations — discrete scheduling rounds instead of an event heap
(cyc and ads_tile decide at a round's end; tp_driven at its own
queue-change instants inside the round), bounded fixed-point
allocation passes instead of the exact sequential queue walk,
current-segment deadline bindings for not-yet-started straddlers —
are documented in ``docs/performance.md#soa-backend``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ...obs import metrics
from .engine import ModeStats, SimReport
from .trace import build_skeleton
from . import soa_kernels as K

__all__ = [
    "SoaOptions",
    "SoaUnsupported",
    "SoaWindowOverflow",
    "soa_supported",
    "build_problem",
    "run_problem",
    "ks_statistic",
    "mean_ci",
    "intervals_overlap",
    "structural_invariants",
]

_TOL = 1e-9


class SoaUnsupported(ValueError):
    """The requested cell is outside the SoA backend's support set."""


class SoaWindowOverflow(SoaUnsupported):
    """A job slid out of the sliding job window still unresolved.

    The window lifetime bound assumes every job resolves within its E2E
    deadline plus the drop-cascade slack; under ``drop_policy="soft"``
    (the runner's default) an overloaded cell legally queues/runs jobs
    past their E2E deadline, and a job that exits the window while
    still PEND/READY/RUN would silently freeze — counted as a miss with
    all its successors starved.  :func:`run_problem` detects this on
    the final state planes and raises instead of returning truncated
    results; callers either widen :attr:`SoaOptions.life_pad_s` (the
    runner's SoA path (``run(spec, seeds=..., backend="soa")``)
    retries with a doubled window automatically) or fall back to the
    scalar/lockstep engines.
    """


def soa_supported(
    policy: str,
    replan_mode: str = "reactive",
    detection_delay_s: float = 0.0,
    drop_policy: str = "soft",
    record: bool = False,
) -> bool:
    """Support predicate mirroring ``batch.fast_lane_supported``'s role:
    the SoA kernels cover the three paper policies (+ elastic cyc) with
    reactive zero-delay replanning under both drop policies; anything
    else (predictive replanning, recorders) must run on the scalar or
    lockstep engines."""
    return (
        policy in K.POLICY_IDS
        and replan_mode == "reactive"
        and abs(detection_delay_s) < _TOL
        and drop_policy in ("soft", "hard")
        and not record
    )


def _drop_mode(policy_name: str, drop_policy: str) -> int:
    """Map (policy, drop_policy) onto the kernel's drop regime.  cyc
    terminates budget overruns at the sub-deadline unconditionally; the
    elastic/tp/ads policies only arm e2e dequeue timers under
    ``drop_policy="hard"`` (the scenario runner defaults to soft)."""
    if policy_name == "cyc":
        return 1
    return 2 if drop_policy == "hard" else 0


@dataclasses.dataclass(frozen=True)
class SoaOptions:
    """Tuning knobs of the discrete-round approximation.

    ``dt_s`` is the scheduling-round cadence.  Event *times* are exact
    regardless (backdated); for cyc and ads_tile dt quantizes when
    decisions are taken (at each round's end): smaller tracks the
    scalar engine's event cadence more closely (the bundled workloads
    see ~one scheduling event per partition per 2-4 ms), larger is
    faster.  tp_driven decides at its queue-change instants inside the
    round, so for it dt only sets how many instants a round batches.
    """

    dt_s: float = 1e-3
    window_round: int = 16      # round the job window up to a multiple
    #: extra seconds added to the job-window lifetime bound (how long a
    #: job may stay unresolved past its release before it slides out of
    #: the window).  The default bound assumes jobs resolve by their
    #: E2E deadline (four of them for tp_driven under soft drops);
    #: under ``drop_policy="soft"`` overload queues jobs past it —
    #: :class:`SoaWindowOverflow` reports when the bound was too tight
    #: and the runner retries with a doubled window.  The effective
    #: lifetime is capped at the horizon (full coverage).
    life_pad_s: float = 0.0
    #: EDF fixed-point refinement steps; None resolves per policy —
    #: tp_driven's event walk needs the exact sequential fixed point
    #: (8), cyc/ads converge by 3 (measured KS-identical vs 8)
    alloc_iters: Optional[int] = None
    bump_passes: int = 8        # tp work-conserving refinement steps
    use_pallas: bool = False    # route the grant select through Pallas


@dataclasses.dataclass
class SoaProblem:
    """One compiled-shape scenario cell plus report-assembly side data."""

    cfg: K.KernelConfig
    const: Dict[str, np.ndarray]
    # job-axis mapping
    jids: np.ndarray            # soa pos -> global skeleton jid (real jobs)
    n_real: int
    n_pad: int
    sen_jids: np.ndarray
    sen_release: np.ndarray
    sen_drop: np.ndarray
    # report side data
    duration: float
    num_tiles: int
    considered: np.ndarray      # (n_pad,) bool
    e2e_host: np.ndarray        # (n_pad,) float64 exact
    sinks: List[Tuple[str, int, float, float, str]]  # (chain, pos, t0, ddl, mode)
    sink_groups: "SinkGroups"
    chain_names: List[str]
    expected: Dict[str, int]
    expected_mode: Dict[str, Dict[str, int]]
    mode_order: List[str]
    seg_mode: List[str]
    seg_span: List[Tuple[float, float]]
    spans: Dict[str, float]
    n_mode_switches: int
    tiles_used: int
    tiles_reserved_mean: float
    frontier_meta: Dict[str, object]
    skeleton_key: tuple
    life: float                 # job-window lifetime bound (seconds)
    win_lo_final: int           # highest window lower bound over rounds


@dataclasses.dataclass(frozen=True)
class SinkGroups:
    """The chain sinks of a problem as index arrays, for assembling
    every lane's report from reductions over ``(R, sinks)``.

    ``pos``, ``t0`` and ``ddl`` are each sink's job position, release of
    its chain's source and chain deadline.  The mode axis ``modes`` is
    the problem's ``spans`` in order, then any other mode a sink falls
    in.  ``chain_mode`` is the ``(sinks, chains * modes)`` one-hot of
    each sink's (chain, mode).  ``chain_ix`` holds each chain's sinks in
    sink order, ``mode_ix`` each mode of ``spans``'s, both padded to the
    longest group where ``chain_ok`` / ``mode_ok`` are False.  ``fill``
    is, per chain, the (mode index, expected sinks) a starvation deficit
    fills in ``mode_order``.
    """

    pos: np.ndarray
    t0: np.ndarray
    ddl: np.ndarray
    modes: Tuple[str, ...]
    chain_mode: np.ndarray
    chain_ix: np.ndarray
    chain_ok: np.ndarray
    mode_ix: np.ndarray
    mode_ok: np.ndarray
    expected: np.ndarray
    fill: Tuple[Tuple[Tuple[int, int], ...], ...]


def _padded_groups(key: np.ndarray, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """The indices where ``key == g`` for each ``g < n``, in order, as
    rows padded to the longest, and the mask of the real entries."""
    rows = [np.flatnonzero(key == g) for g in range(n)]
    L = max((len(r) for r in rows), default=0)
    ix = np.zeros((n, L), dtype=np.int64)
    ok = np.zeros((n, L), dtype=bool)
    for g, r in enumerate(rows):
        ix[g, : len(r)] = r
        ok[g, : len(r)] = True
    return ix, ok


def _sink_groups(sinks, chain_names, spans, expected, expected_mode,
                 mode_order) -> SinkGroups:
    cname, pos, t0, ddl, smode = zip(*sinks) if sinks else ((),) * 5
    modes = list(spans)
    modes += sorted(set(smode) - set(modes))
    c_of = {c: i for i, c in enumerate(chain_names)}
    m_of = {m: j for j, m in enumerate(modes)}
    chain = np.array(list(map(c_of.__getitem__, cname)), dtype=np.int64)
    mode = np.array(list(map(m_of.__getitem__, smode)), dtype=np.int64)
    C, M = len(chain_names), len(modes)
    chain_mode = np.zeros((len(sinks), C * M))
    chain_mode[np.arange(len(sinks)), chain * M + mode] = 1.0
    chain_ix, chain_ok = _padded_groups(chain, C)
    mode_ix, mode_ok = _padded_groups(mode, len(spans))
    return SinkGroups(
        pos=np.array(pos, dtype=np.int64),
        t0=np.array(t0, dtype=np.float64),
        ddl=np.array(ddl, dtype=np.float64),
        modes=tuple(modes),
        chain_mode=chain_mode,
        chain_ix=chain_ix,
        chain_ok=chain_ok,
        mode_ix=mode_ix,
        mode_ok=mode_ok,
        expected=np.array([expected[c] for c in chain_names], dtype=np.int64),
        fill=tuple(
            tuple((m_of[m], em[m]) for m in mode_order if m in em)
            for em in (expected_mode[c] for c in chain_names)
        ),
    )


def _policy_knobs(policy) -> Tuple[bool, bool, bool, float]:
    """(admission, quota_control, slack_sharing, realloc_gate) of a
    policy *instance* (ads ablation flags ride into the kernel config)."""
    return (
        bool(getattr(policy, "admission", True)),
        bool(getattr(policy, "quota_control", True)),
        bool(getattr(policy, "slack_sharing", True)),
        float(getattr(policy, "realloc_gate", 1.0)),
    )


def _downstream_budget(wf, sched) -> Dict[str, float]:
    """ads slack sharing: tightest downstream budget per task under one
    table (AdsTilePolicy.setup's ``_down``)."""
    down: Dict[str, float] = {}
    for t, task in wf.tasks.items():
        if task.is_sensor:
            continue
        tight = math.inf
        for chain in wf.chain_for(t):
            i = chain.nodes.index(t)
            after = [
                n for n in chain.nodes[i + 1:] if not wf.tasks[n].is_sensor
            ]
            tight = min(tight, sum(sched.plans[n].budget_s for n in after))
        down[t] = 0.0 if tight is math.inf else tight
    return down


def _candidate_table(wf, sched, policy_name) -> Dict[str, Tuple[int, ...]]:
    """Per-task DoP ladders as the policy instance would resolve them:
    ads follows an autotuned table's compiled candidate set
    (``meta["task_dop_candidates"]``), tp always uses the workload
    ladder, cyc only ever uses the planned DoP."""
    src = sched.meta.get("task_dop_candidates") if policy_name == "ads_tile" else None
    out = {}
    for name, t in wf.tasks.items():
        if t.is_sensor:
            continue
        if src is not None:
            out[name] = tuple(src.get(name, t.dop_candidates()))
        else:
            out[name] = t.dop_candidates()
    return out


def _segments(scenario, duration, schedule0, portfolio, replan):
    """Scenario boundary spans clipped to the horizon, each carrying the
    schedule table active during it and whether its entry performs a
    hot-swap (mirrors the reactive replanner: swap only when the
    portfolio's table for the new mode differs from the active one)."""
    bounds = list(scenario.boundaries())
    segs = []
    active = schedule0
    for i, (t, m) in enumerate(bounds):
        if t >= duration - _TOL and i > 0:
            break
        t_end = bounds[i + 1][0] if i + 1 < len(bounds) else max(duration, t)
        t_end = min(t_end, duration)
        swap = False
        if i > 0 and replan and portfolio is not None:
            tbl = portfolio.get(m)
            if tbl is not None and tbl is not active:
                active = tbl
                swap = True
        segs.append((max(0.0, t), t_end, m, active, swap))
    return segs


def _plan_deltas_staged(wf, old, new, P) -> np.ndarray:
    """Hot-swap stage-in volume per *target* partition (engine
    ``_plan_deltas``): full checkpoint x dop on a partition move, the
    L2P minimal checkpoint x |dop delta| on a DoP change in place."""
    staged = np.zeros(P, dtype=np.float64)
    for task, np_plan in new.plans.items():
        op = old.plans.get(task)
        if op is None:
            continue
        ckpt = wf.tasks[task].checkpoint_bytes
        if np_plan.partition != op.partition:
            staged[np_plan.partition] += ckpt * np_plan.dop
        elif np_plan.dop != op.dop:
            staged[np_plan.partition] += ckpt * abs(np_plan.dop - op.dop)
    return staged


def build_problem(
    wf,
    model,
    schedule0,
    portfolio,
    policy,
    scenario,
    duration: float,
    replan: bool = True,
    n_lanes: int = 8,
    drop_policy: str = "soft",
    options: Optional[SoaOptions] = None,
) -> SoaProblem:
    """Precompute one scenario cell's lane-independent arrays.

    ``policy`` may be a policy instance (ads ablation flags are read
    off it) or a policy name string.
    """
    opt = options or SoaOptions()
    hw = model.hw
    policy_name = policy if isinstance(policy, str) else policy.name
    if policy_name not in K.POLICY_IDS:
        raise SoaUnsupported(f"policy {policy_name!r} not supported by soa")
    admission, quota_control, slack_sharing, gate = (
        (True, True, True, 1.0)
        if isinstance(policy, str)
        else _policy_knobs(policy)
    )
    if getattr(policy, "drop_on_subddl", False):
        raise SoaUnsupported("tp_driven drop_on_subddl is scalar-only")

    skel = build_skeleton(wf, scenario, duration)
    rel_all = np.asarray(skel.release, dtype=np.float64)
    dnn = np.asarray(skel.dnn_ix, dtype=np.int64)
    sen = np.asarray(skel.sen_ix, dtype=np.int64)

    order = np.lexsort((dnn, rel_all[dnn]))
    jids = dnn[order]
    n_real = len(jids)
    rel = rel_all[jids]

    tasks_pos = [skel.tasks[j] for j in jids]
    task_names = sorted({t for t in tasks_pos})
    tid = {t: i for i, t in enumerate(task_names)}
    task_idx = np.array([tid[t] for t in tasks_pos], dtype=np.int64)

    ddl_off = np.array(
        [wf.deadline_offset(t) for t in task_names], dtype=np.float64
    )
    e2e = rel + ddl_off[task_idx]
    if not np.all(np.isfinite(e2e)):
        raise SoaUnsupported(
            "DNN task without a finite E2E deadline (unbounded job "
            "lifetime breaks the windowed job axis)"
        )
    sync_t = np.array(
        [model.profiles[t].sync_per_tile_s for t in task_names],
        dtype=np.float64,
    )
    ckpt_t = np.array(
        [wf.tasks[t].checkpoint_bytes for t in task_names], dtype=np.float64
    )

    # ---- segments, tables, partitions --------------------------------
    segs = _segments(scenario, duration, schedule0, portfolio, replan)
    S = len(segs)
    tables = [s[3] for s in segs]
    P = max(
        max((pp.index for pp in tbl.partitions), default=0) + 1
        for tbl in tables
    )

    # ---- round grid ---------------------------------------------------
    dt = float(opt.dt_s)
    t0s, t1s, seg_ix, entry = [], [], [], []
    for s, (a, b, _m, _tbl, _sw) in enumerate(segs):
        n = max(1, int(math.ceil((b - a) / dt - 1e-9)))
        edges = a + (b - a) * np.arange(n + 1) / n
        for k in range(n):
            t0s.append(edges[k])
            t1s.append(edges[k + 1])
            seg_ix.append(s)
            entry.append(k == 0)
    t0s = np.asarray(t0s)
    t1s = np.asarray(t1s)
    n_rounds = len(t0s)

    # ---- job windows --------------------------------------------------
    # lifetime bound: jobs normally resolve by their E2E deadline (plus
    # one dependency hop per round for the drop cascade).  Under
    # drop_mode 0 overload legally queues jobs past the E2E deadline:
    # ``life_pad_s`` widens the bound, the cap at the horizon makes a
    # wide-enough retry always possible, and run_problem's post-check
    # raises SoaWindowOverflow if the bound still proved too tight
    # (never silently truncates).
    max_hops = max((len(c.nodes) for c in wf.chains), default=4)
    cascade = (max_hops + 4) * dt
    life = float(np.max(ddl_off[np.isfinite(ddl_off)])) + cascade
    if policy_name == "tp_driven" and _drop_mode(policy_name, drop_policy) == 0:
        # tp_driven's work-conserving walks keep a job queued for several
        # deadlines: on x1 rate_churn 4 drives in 8,064 outlived twice the
        # bound, none three times, 1 in about 18,000 0.41 s.  Each overflow
        # costs a retry and a compile, while four bounds (W 144 -> 256 at
        # R=64) cost a v5e call 3% more than two
        life *= 4
    life = min(max(life + float(opt.life_pad_s), 2 * dt), duration + cascade)
    lo = np.searchsorted(rel, t1s - life, side="left")
    hi = np.searchsorted(rel, t1s, side="right")
    wr = int(opt.window_round)
    W = int(max(8, ((int(np.max(hi - lo)) + wr - 1) // wr) * wr))
    lo = np.minimum(lo, np.maximum(hi - W, 0)).astype(np.int32)
    n_pad = int(max(n_real, int(np.max(lo)) + W))

    def padf(a, fill):
        out = np.full(n_pad, fill, dtype=np.float64)
        out[:n_real] = a
        return out

    rel_p = padf(rel, np.inf)
    e2e_p = padf(e2e, np.inf)
    sync_p = padf(sync_t[task_idx], 0.0)
    ckpt_p = padf(ckpt_t[task_idx], 0.0)

    # ---- finish-code columns (jobs, then sensors, then dummy) --------
    n_sen = len(sen)
    A1 = n_pad + n_sen + 1
    col_of = np.full(int(max(rel_all.shape[0], 1)), A1 - 1, dtype=np.int64)
    col_of[jids] = np.arange(n_real)
    col_of[sen] = n_pad + np.arange(n_sen)

    # predecessors from the skeleton's successor lists
    preds_l: List[List[int]] = [[] for _ in range(n_real)]
    pos_of = np.full_like(col_of, -1)
    pos_of[jids] = np.arange(n_real)
    for j, succs in enumerate(skel.succs):
        for sjid in succs:
            p = pos_of[sjid]
            if p >= 0:
                preds_l[p].append(int(col_of[j]))
    PM = max(1, max((len(p) for p in preds_l), default=1))
    preds = np.full((n_pad, PM), A1 - 1, dtype=np.int32)
    for p, lst in enumerate(preds_l):
        preds[p, : len(lst)] = lst

    # ---- per-segment schedule bindings --------------------------------
    cand_tbl = [_candidate_table(wf, tbl, policy_name) for tbl in tables]
    C = max(
        1, max(len(c) for ct in cand_tbl for c in ct.values())
    ) if policy_name in ("tp_driven", "ads_tile") else 1

    T = len(task_names)
    ert = np.full((S, n_pad), np.inf, dtype=np.float64)
    sub = np.full((S, n_pad), np.inf, dtype=np.float64)
    tgt = np.full((S, n_pad), np.inf, dtype=np.float64)
    pdop = np.ones((S, n_pad), dtype=np.float64)
    part = np.zeros((S, n_pad), dtype=np.float64)
    cands = np.ones((S, n_pad, C), dtype=np.float64)
    caps = np.zeros((S, P), dtype=np.float64)
    hops = np.ones((S, P), dtype=np.float64)
    staged = np.zeros((S, P), dtype=np.float64)
    swap = np.zeros(S, dtype=bool)

    for s, (a, b, m, tbl, sw) in enumerate(segs):
        ert_o = np.zeros(T)
        sub_o = np.zeros(T)
        dop_o = np.ones(T)
        par_o = np.zeros(T)
        dwn_o = np.zeros(T)
        cnd_o = np.ones((T, C))
        down = _downstream_budget(wf, tbl) if policy_name == "ads_tile" else {}
        for t, i in tid.items():
            plan = tbl.plans[t]
            ert_o[i] = plan.ert_s
            sub_o[i] = plan.subdeadline_s
            dop_o[i] = plan.dop
            par_o[i] = plan.partition
            dwn_o[i] = down.get(t, 0.0)
            if C > 1 or policy_name in ("tp_driven", "ads_tile"):
                ladder = cand_tbl[s][t]
                cnd_o[i, : len(ladder)] = ladder
                cnd_o[i, len(ladder):] = ladder[-1]
        ert[s, :n_real] = rel + ert_o[task_idx]
        sub[s, :n_real] = rel + sub_o[task_idx]
        if policy_name == "ads_tile" and slack_sharing:
            tgt[s, :n_real] = np.maximum(sub[s, :n_real], e2e - dwn_o[task_idx])
        else:
            tgt[s, :n_real] = sub[s, :n_real]
        pdop[s, :n_real] = dop_o[task_idx]
        part[s, :n_real] = par_o[task_idx]
        cands[s, :n_real, :] = cnd_o[task_idx]
        for pp in tbl.partitions:
            caps[s, pp.index] = pp.capacity
            hops[s, pp.index] = hw.avg_hops_to_mc(max(pp.capacity, 1))
        if sw:
            swap[s] = True
            staged[s] = _plan_deltas_staged(wf, tables[s - 1], tbl, P)

    # ---- per-round EDF permutations -----------------------------------
    perm = np.zeros((n_rounds, W), dtype=np.int32)
    iperm = np.zeros((n_rounds, W), dtype=np.int32)
    arangeW = np.arange(W)
    for r in range(n_rounds):
        if policy_name in ("cyc", "cyc_s"):
            key = ert[seg_ix[r], lo[r]: lo[r] + W]
            key2 = sub[seg_ix[r], lo[r]: lo[r] + W]
            o = np.lexsort((arangeW, key2, key))
        else:
            key = sub[seg_ix[r], lo[r]: lo[r] + W]
            o = np.lexsort((arangeW, key))
        perm[r] = o
        iperm[r][o] = arangeW

    f4 = np.float32
    const = {
        "release": rel_p.astype(f4),
        "e2e": e2e_p.astype(f4),
        "sync": sync_p.astype(f4),
        "ckpt": ckpt_p.astype(f4),
        "preds": preds,
        "ert": ert.astype(f4),
        "sub": sub.astype(f4),
        "tgt": tgt.astype(f4),
        "pdop": pdop.astype(f4),
        "part": part.astype(f4),
        "cands": cands.astype(f4),
        "caps": caps.astype(f4),
        "hops": hops.astype(f4),
        "staged": staged.astype(f4),
        "swap": swap,
        "t0": t0s.astype(f4),
        "t1": t1s.astype(f4),
        "seg": np.asarray(seg_ix, dtype=np.int32),
        "lo": lo.astype(np.int32),
        "entry": np.asarray(entry, dtype=bool),
        "perm": perm,
        "iperm": iperm,
    }

    cfg = K.KernelConfig(
        policy=K.POLICY_IDS[policy_name],
        R=int(n_lanes),
        W=W,
        C=C,
        PM=PM,
        P=P,
        tile_flops=float(hw.tile_flops),
        fixed_s=float(hw.realloc.fixed_s),
        decision_s=float(hw.realloc.decision_s),
        per_hop_s=float(hw.realloc.per_hop_s),
        inv_bw=float(1.0 / hw.realloc.migration_bw),
        realloc_gate=gate,
        admission=admission,
        quota_control=quota_control,
        drop_mode=_drop_mode(policy_name, drop_policy),
        alloc_iters=int(
            opt.alloc_iters
            if opt.alloc_iters is not None
            else (8 if policy_name == "tp_driven" else 3)
        ),
        bump_passes=int(opt.bump_passes),
        use_pallas=bool(opt.use_pallas),
    )

    # ---- report-assembly side data ------------------------------------
    considered = np.zeros(n_pad, dtype=bool)
    # strict comparisons to mirror the scalar report exactly: float64
    # release/deadline arithmetic lands on the same values in both
    # backends, so a tolerance here would only *dis*agree at boundaries
    # (e.g. 1.9 + 0.1 > 2.0 in binary64)
    considered[:n_real] = (rel <= duration) & (e2e <= duration)

    chain_ddl = {c.name: c.deadline_s for c in wf.chains}
    sinks = []
    for (cname, jid), t0 in skel.sink_src.items():
        p = int(pos_of[jid]) if jid < len(pos_of) else -1
        if p < 0:
            continue
        sinks.append(
            (cname, p, float(t0), float(chain_ddl[cname]), scenario.mode_at(t0))
        )
    sinks.sort(key=lambda x: x[2])
    expected: Dict[str, int] = {c.name: 0 for c in wf.chains}
    expected_mode: Dict[str, Dict[str, int]] = {c.name: {} for c in wf.chains}
    for cname, _p, t0, ddl, m in sinks:
        if t0 + ddl <= duration:
            expected[cname] += 1
            em = expected_mode[cname]
            em[m] = em.get(m, 0) + 1

    bounds = list(scenario.boundaries())
    ends = [t for t, _m in bounds[1:]]
    ends.append(max(duration, bounds[-1][0]))
    spans: Dict[str, float] = {}
    for (bt0, m), bt1 in zip(bounds, ends):
        spans[m] = spans.get(m, 0.0) + max(
            0.0, min(bt1, duration) - min(bt0, duration)
        )
    n_switch = sum(1 for t, _m in bounds[1:] if t <= duration + _TOL)

    mode_order = [m for m in scenario.modes()]
    reserved = sum((b - a) * tbl.peak_tiles for a, b, _m, tbl, _sw in segs)
    tiles_used = max(tbl.peak_tiles for tbl in [schedule0] + tables)

    return SoaProblem(
        cfg=cfg,
        const=const,
        jids=jids,
        n_real=n_real,
        n_pad=n_pad,
        sen_jids=sen,
        sen_release=rel_all[sen],
        sen_drop=np.array(
            [skel.drop_at_release[j] for j in sen], dtype=bool
        ),
        duration=float(duration),
        num_tiles=int(hw.num_tiles),
        considered=considered,
        e2e_host=e2e_p,
        sinks=sinks,
        sink_groups=_sink_groups(
            sinks, [c.name for c in wf.chains], spans, expected,
            expected_mode, mode_order,
        ),
        chain_names=[c.name for c in wf.chains],
        expected=expected,
        expected_mode=expected_mode,
        mode_order=mode_order,
        seg_mode=[m for _a, _b, m, _t, _s in segs],
        seg_span=[(a, b) for a, b, _m, _t, _s in segs],
        spans=spans,
        n_mode_switches=n_switch,
        tiles_used=int(tiles_used),
        tiles_reserved_mean=float(reserved / duration),
        frontier_meta=dict(schedule0.meta.get("autotune") or {}),
        skeleton_key=skel.key,
        life=float(life),
        win_lo_final=int(lo.max()) if n_rounds else 0,
    )


# ---------------------------------------------------------------------------
# lane data + execution
# ---------------------------------------------------------------------------
def _lanes(problem: SoaProblem, btrace) -> Dict[str, np.ndarray]:
    R = len(btrace.seeds)
    f4 = np.float32
    work = np.zeros((R, problem.n_pad), dtype=f4)
    io = np.zeros((R, problem.n_pad), dtype=f4)
    work[:, : problem.n_real] = btrace.work[:, problem.jids]
    io[:, : problem.n_real] = btrace.io[:, problem.jids]

    n_sen = len(problem.sen_jids)
    A1 = problem.n_pad + n_sen + 1
    codes0 = np.full((R, A1), np.inf, dtype=f4)
    codes0[:, A1 - 1] = 0.0
    lat = btrace.sensor_lat[:, problem.sen_jids]
    fin = problem.sen_release[None, :] + lat
    codes0[:, problem.n_pad: A1 - 1] = np.where(
        problem.sen_drop[None, :],
        -problem.sen_release[None, :] - 1.0,
        fin,
    )
    if problem.cfg.policy == K.POLICY_IDS["tp_driven"]:
        # tp_driven's walks read their instants to better than float32:
        # after the codes come what float32 rounded away from each
        # sensor's time, as the kernel decodes it
        codes0_lo = np.zeros((R, A1), dtype=f4)
        sen = codes0[:, problem.n_pad: A1 - 1]
        decoded = np.where(sen < 0, -sen - f4(1.0), sen)
        t = np.where(problem.sen_drop[None, :], problem.sen_release[None, :], fin)
        codes0_lo[:, problem.n_pad: A1 - 1] = t - decoded.astype(np.float64)
        codes0 = np.concatenate([codes0, codes0_lo], axis=1)
    return {"work": work, "io": io, "codes0": codes0}


def run_problem(
    problem: SoaProblem, btrace, seeds: Sequence[int]
) -> List[SimReport]:
    """Advance all lanes through the compiled round loop and assemble
    one scalar-shaped :class:`SimReport` per seed."""
    if problem.cfg.R != len(seeds):
        raise ValueError(
            f"problem compiled for R={problem.cfg.R}, got {len(seeds)} seeds"
        )
    with metrics.phase("soa_lanes"):
        lanes = _lanes(problem, btrace)
    out = K.simulate(problem.cfg, problem.const, lanes)
    # jobs below the final window lower bound had their window close
    # before the horizon end; any still unresolved there froze mid-queue
    # (overload past the lifetime bound) and the lane's report would
    # silently miscount it as a miss and starve its successors
    cut = min(problem.win_lo_final, problem.n_real)
    if cut > 0:
        stuck = out["state"][:, :cut] < K.DONE
        if np.any(stuck):
            n_lanes = int(np.sum(np.any(stuck, axis=1)))
            n_jobs = int(np.max(np.sum(stuck, axis=1)))
            metrics.count("soa_window_overflows")
            metrics.count("soa_overflow_lanes", n_lanes)
            raise SoaWindowOverflow(
                f"up to {n_jobs} job(s) per lane slid out of the "
                f"{problem.life:.3f}s SoA job window unresolved "
                f"({n_lanes}/{problem.cfg.R} lanes affected): the cell "
                "queues jobs past the E2E-deadline lifetime bound "
                "(overload under drop_policy='soft').  Widen "
                "SoaOptions.life_pad_s (the runner's SoA path retries with a "
                "doubled window automatically) or use the scalar/"
                "lockstep backend for this cell."
            )
    with metrics.phase("soa_assemble"):
        return _assemble_reports(problem, out)


def _assemble_reports(problem: SoaProblem, out: Dict[str, np.ndarray]):
    """One report per lane: reductions over all lanes at once, then
    each lane's report objects from its rows."""
    with metrics.phase("soa_assemble_arrays"):
        arrays = _report_arrays(problem, out)
    with metrics.phase("soa_assemble_lanes"):
        return [_lane_report(problem, arrays, k) for k in range(problem.cfg.R)]


def _row_p99(x: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """``np.percentile(x[i][mask[i]], 99)`` for every row ``i`` of the
    last axis at once, bit for bit (NumPy's ``linear`` method and its
    ``_lerp``); nan where a row has no masked value."""
    n = mask.sum(axis=-1)
    if x.shape[-1] == 0:
        return np.full(n.shape, np.nan)
    s = np.sort(np.where(mask, x, np.inf), axis=-1)
    v = (n - 1) * 0.99
    lo = np.floor(v)
    t = v - lo
    i = np.maximum(lo.astype(np.intp), 0)
    j = np.minimum(i + 1, np.maximum(n - 1, 0))
    a = np.take_along_axis(s, i[..., None], axis=-1)[..., 0]
    b = np.take_along_axis(s, j[..., None], axis=-1)[..., 0]
    a[n == 0] = np.nan
    d = b - a
    return np.where(t >= 0.5, b - d * (1 - t), a + d * t)


def _report_arrays(problem: SoaProblem, out: Dict[str, np.ndarray]) -> dict:
    """Every lane's report fields as reductions over ``(R, sinks)``,
    then as Python values by lane for :func:`_lane_report`."""
    R = problem.cfg.R
    g = problem.sink_groups
    C, M, Ms = len(problem.chain_names), len(g.modes), len(problem.spans)
    cons = problem.considered
    state = out["state"]
    fin = out["fin"].astype(np.float64)
    deg = out["deg"] > 0.5

    dropped = (state == K.DROP) & cons[None, :]
    late = (state == K.DONE) & cons[None, :] & (fin > problem.e2e_host[None, :] + 1e-6)
    unfinished = (state < K.DONE) & cons[None, :]
    n_dropped = dropped.sum(axis=1)
    n_miss = n_dropped + late.sum(axis=1) + unfinished.sum(axis=1)

    # each lane's outcome per sink
    st_s = state[:, g.pos]
    lat_s = fin[:, g.pos] - g.t0[None, :]
    done_s = st_s == K.DONE
    drop_s = st_s == K.DROP
    viol_s = done_s & ((lat_s > g.ddl[None, :] + 1e-9) | deg[:, g.pos])

    # per (chain, mode): [completed or dropped, violated or dropped]
    flags = np.concatenate([done_s, drop_s, viol_s]).astype(np.float64)
    sums = (flags @ g.chain_mode).astype(np.int64).reshape(3, R, C, M)
    rec_n = sums[0] + sums[1]
    rec_v = sums[2] + sums[1]
    count = rec_n.sum(axis=2)
    viol = rec_v.sum(axis=2)

    # starvation deficits, reconciled chronologically per mode
    deficit = np.maximum(g.expected[None, :] - count, 0)
    count += deficit
    viol += deficit
    lanes, chains = np.nonzero(deficit)
    for k, c, d in zip(lanes.tolist(), chains.tolist(),
                       deficit[lanes, chains].tolist()):
        for j, em in g.fill[c]:
            take = min(max(0, em - int(rec_n[k, c, j])), d)
            rec_n[k, c, j] += take
            rec_v[k, c, j] += take
            d -= take
            if not d:
                break
    metrics.count("soa_assemble_deficit_lanes", len(np.unique(lanes)))

    # latencies of the completed sinks: per chain in sink order, per mode
    lat_c = lat_s[:, g.chain_ix]
    done_c = done_s[:, g.chain_ix] & g.chain_ok
    lat_ends = np.zeros(R * C + 1, dtype=np.int64)
    np.cumsum(done_c.sum(axis=2), out=lat_ends[1:])
    mode_p99 = _row_p99(lat_s[:, g.mode_ix], done_s[:, g.mode_ix] & g.mode_ok)

    busy_seg, rel_seg = out["busy"], out["realloc"]
    mode_busy = np.zeros((R, Ms))
    mode_rel = np.zeros((R, Ms))
    for s, m in enumerate(problem.seg_mode):
        j = g.modes.index(m)
        if j < Ms:
            mode_busy[:, j] += busy_seg[:, s]
            mode_rel[:, j] += rel_seg[:, s]

    return {
        "n_jobs": int(np.sum(cons)),
        "n_dropped": n_dropped.tolist(),
        "n_miss": n_miss.tolist(),
        "count": count.tolist(),
        "viol": viol.tolist(),
        "p99": _row_p99(lat_c, done_c).tolist(),
        "lat_flat": lat_c[done_c].tolist(),
        "lat_ends": lat_ends.tolist(),
        "mode_n": rec_n.sum(axis=1)[:, :Ms].tolist(),
        "mode_v": rec_v.sum(axis=1)[:, :Ms].tolist(),
        "mode_p99": mode_p99.tolist(),
        "mode_busy": mode_busy.tolist(),
        "mode_rel": mode_rel.tolist(),
        "busy": busy_seg.sum(axis=1).tolist(),
        "rel": rel_seg.sum(axis=1).tolist(),
        "dropped_work": out["dropped_work"].tolist(),
        "n_realloc": out["n_realloc"].tolist(),
        "realloc_bytes": out["realloc_bytes"].tolist(),
    }


def _lane_report(problem: SoaProblem, a: dict, k: int) -> SimReport:
    """Lane ``k``'s report from the rows of :func:`_report_arrays`."""
    dur, n_jobs = problem.duration, a["n_jobs"]
    total = problem.num_tiles * dur
    names = problem.chain_names
    flat, ends, at = a["lat_flat"], a["lat_ends"], k * len(names)
    mode_stats: Dict[str, ModeStats] = {}
    for j, (m, span) in enumerate(problem.spans.items()):
        denom = problem.num_tiles * span
        mode_stats[m] = ModeStats(
            mode=m,
            span_s=span,
            n_completed=a["mode_n"][k][j],
            n_violations=a["mode_v"][k][j],
            p99_s=a["mode_p99"][k][j],
            effective_frac=a["mode_busy"][k][j] / denom if denom > 0 else 0.0,
            realloc_frac=a["mode_rel"][k][j] / denom if denom > 0 else 0.0,
        )
    busy, rel_ts = a["busy"][k], a["rel"][k]
    return SimReport(
        duration_s=dur,
        total_tiles=problem.num_tiles,
        effective_frac=busy / total,
        realloc_frac=rel_ts / total,
        idle_frac=max(0.0, 1.0 - (busy + rel_ts) / total),
        dropped_work_frac=a["dropped_work"][k] / total,
        n_realloc=int(round(a["n_realloc"][k])),
        realloc_bytes=a["realloc_bytes"][k],
        n_jobs=n_jobs,
        n_dropped=a["n_dropped"][k],
        task_miss_rate=a["n_miss"][k] / max(n_jobs, 1),
        chain_count=dict(zip(names, a["count"][k])),
        chain_violations=dict(zip(names, a["viol"][k])),
        chain_p99_s=dict(zip(names, a["p99"][k])),
        chain_latencies={
            c: flat[ends[at + i]: ends[at + i + 1]] for i, c in enumerate(names)
        },
        decision_ratios=[],
        mode_stats=mode_stats,
        n_mode_switches=problem.n_mode_switches,
        forecast=None,
        tiles_used=problem.tiles_used,
        tiles_reserved_mean=problem.tiles_reserved_mean,
        frontier_meta=dict(problem.frontier_meta),
    )


# ---------------------------------------------------------------------------
# distributional-equivalence machinery
# ---------------------------------------------------------------------------
def ks_statistic(a: Sequence[float], b: Sequence[float]) -> float:
    """Two-sample Kolmogorov-Smirnov statistic (sup ECDF distance)."""
    a = np.sort(np.asarray(a, dtype=np.float64))
    b = np.sort(np.asarray(b, dtype=np.float64))
    if len(a) == 0 or len(b) == 0:
        return 0.0 if len(a) == len(b) else 1.0
    pool = np.concatenate([a, b])
    ca = np.searchsorted(a, pool, side="right") / len(a)
    cb = np.searchsorted(b, pool, side="right") / len(b)
    return float(np.max(np.abs(ca - cb)))


def mean_ci(xs: Sequence[float], z: float = 1.96) -> Tuple[float, float]:
    """Normal-approximation confidence interval of the mean."""
    x = np.asarray(xs, dtype=np.float64)
    m = float(np.mean(x))
    if len(x) < 2:
        return m, m
    half = z * float(np.std(x, ddof=1)) / math.sqrt(len(x))
    return m - half, m + half


def intervals_overlap(
    a: Tuple[float, float], b: Tuple[float, float], pad: float = 0.0
) -> bool:
    return a[0] - pad <= b[1] and b[0] - pad <= a[1]


def structural_invariants(report: SimReport) -> Dict[str, object]:
    """The exactly-matched facts of a run: job universe, seam structure,
    chain universe and reservation footprint.  Both engines must agree
    on these bit-for-bit (they are schedule/skeleton facts, not
    sampling outcomes)."""
    return {
        "n_jobs": report.n_jobs,
        "n_mode_switches": report.n_mode_switches,
        "chains": tuple(sorted(report.chain_count)),
        "mode_spans": tuple(
            sorted((m, round(s.span_s, 9)) for m, s in report.mode_stats.items())
        ),
        "total_tiles": report.total_tiles,
        "tiles_used": report.tiles_used,
        "tiles_reserved_mean": round(report.tiles_reserved_mean, 6),
        "duration_s": report.duration_s,
    }
