"""What decides ``correct``: the program's drives against the reference's.

The SoA backend agrees with the scalar engine distributionally, not bit
for bit: it takes scheduling decisions on a 1-ms round grid, so a
drive's event times differ wherever a decision moved.  The numbers
compared, over a sample of drives that the timed calls returned, each
against the reference's drive of the same seed:

* ``struct_bad`` -- drives whose structural invariants (job universe,
  seams, chain universe, reservation footprint) differ.  Exact: limit 0.
* ``match_ratio`` -- for the worst drive: the program's chain
  latencies that lie within ``MATCH_TOL_S`` of one of the reference's
  for the same chain in a *different* drive (chance), over those that
  do in the same drive, each count plus one.  Backdated event times
  reproduce a clean chain's latency to float32 rounding, so a sound
  drive matches its own reference drive far above chance; a drive
  simulated with another seed's draws, a lane left out and filled in,
  a latency altered, or planes rounded to a lower precision fall to
  chance (ratio about 1).
* ``viol_bias`` -- absolute mean over drives of (program's violation
  rate - reference's).
* ``ks`` -- two-sample Kolmogorov-Smirnov statistic of the pooled chain
  latencies.

``ks_statistic``, ``mean_ci``, ``intervals_overlap``,
``structural_invariants`` and ``compare_distributional`` are copies of
the system's own gate arithmetic (``repro.core.sim.soa`` and
``benchmarks/check_equivalence.py``), kept here so that a change to the
system cannot move the yardstick.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

#: two latencies "match" within this many seconds: float32 event times
#: over a 2-s horizon carry about 2e-7 s of rounding
MATCH_TOL_S = 2e-6

NUMBERS = ("struct_bad", "match_ratio", "viol_bias", "ks")


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


def structural_invariants(report) -> Dict[str, object]:
    """The exactly-matched facts of a run: job universe, seam structure,
    chain universe and reservation footprint."""
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


def compare_distributional(ref, soa, ks_tol: float) -> dict:
    """Verdicts of SoA reports ``soa`` against oracle reports ``ref`` of
    the same seeds: exact structural invariants per seed, pooled
    chain-latency KS, and CI overlap on the summary rates."""
    struct_ok = all(
        structural_invariants(a) == structural_invariants(b) for a, b in zip(ref, soa)
    )
    lat_ref = [x for r in ref for ls in r.chain_latencies.values() for x in ls]
    lat_soa = [x for r in soa for ls in r.chain_latencies.values() for x in ls]
    ks = ks_statistic(lat_ref, lat_soa)
    ci = {}
    for metric in ("violation_rate", "realloc_frac", "tiles_reserved_mean"):
        ci_ref = mean_ci([getattr(r, metric) for r in ref])
        ci_soa = mean_ci([getattr(r, metric) for r in soa])
        ci[metric] = (ci_ref, ci_soa, intervals_overlap(ci_ref, ci_soa, pad=1e-9))
    return {
        "struct_ok": struct_ok,
        "ks": ks,
        "ks_ok": ks <= ks_tol,
        "ci": ci,
        "ci_ok": all(ok for _r, _s, ok in ci.values()),
        "n": (len(lat_ref), len(lat_soa)),
    }


def _matches(got, ref) -> Tuple[int, int]:
    """(latencies of ``got`` within MATCH_TOL_S of one of ``ref``'s in
    the same chain, latencies of ``got``)."""
    hit = total = 0
    for chain, lats in got.chain_latencies.items():
        x = np.asarray(lats, dtype=np.float64)
        total += x.size
        a = np.sort(np.asarray(ref.chain_latencies.get(chain, ()), dtype=np.float64))
        if not (x.size and a.size):
            continue
        i = np.clip(np.searchsorted(a, x), 1, max(a.size - 1, 1))
        near = np.minimum(np.abs(a[i - 1] - x), np.abs(a[np.minimum(i, a.size - 1)] - x))
        hit += int(np.sum(near <= MATCH_TOL_S))
    return hit, total


def numbers(ref: List, got: List) -> Dict[str, float]:
    """The compared numbers of program drives ``got`` against reference
    drives ``ref`` of the same seeds, in the same order."""
    if len(ref) != len(got) or not ref:
        raise ValueError(f"{len(got)} program drives against {len(ref)} reference drives")
    k = len(ref)
    struct_bad = sum(
        structural_invariants(a) != structural_invariants(b) for a, b in zip(ref, got)
    )
    match_ratio = max(
        (_matches(got[i], ref[(i + 1) % k])[0] + 1) / (_matches(got[i], ref[i])[0] + 1)
        for i in range(k)
    )
    viol_bias = abs(
        float(np.mean([g.violation_rate - r.violation_rate for r, g in zip(ref, got)]))
    )
    lat_ref = [x for r in ref for ls in r.chain_latencies.values() for x in ls]
    lat_got = [x for r in got for ls in r.chain_latencies.values() for x in ls]
    return {
        "struct_bad": float(struct_bad),
        "match_ratio": float(match_ratio),
        "viol_bias": viol_bias,
        "ks": ks_statistic(lat_ref, lat_got),
    }


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number at or under its limit (a missing number fails)."""
    return all(
        name in values and values[name] <= limit for name, limit in limits.items()
    )
