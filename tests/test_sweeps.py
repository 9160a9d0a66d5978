"""Fleet-scale sweep service: cell keys, cache, manifests, reducer,
and the redesigned ``run()`` entry point.

Contracts under test (docs/sweeps.md):

* cell keys move when any row-relevant input moves and hold still
  under recomputation and derived attachments (portfolio, mode_defs);
* a repeated identical campaign is 100% cache-hit (zero cells
  executed) and serves rows equal to the fresh ones;
* an interrupted campaign resumed from its manifest equals the
  uninterrupted run row for row;
* a crashing cell is captured per cell — finished rows persist, the
  manifest lists the failed keys, and rerunning retries failures only;
* ``SweepReducer`` streaming equals batch ``aggregate_sweep``;
* the deprecated entry points delegate to ``run()`` bit-identically
  while warning.
"""
import dataclasses
import json
import os

import pytest

from repro.core.sim.batch import reports_identical
from repro.scenarios import aggregate_sweep, sweep
from repro.scenarios.runner import (
    SWEEP_BACKENDS,
    ScenarioSpec,
    parallel_map,
    run,
    summarize,
)
from repro.scenarios.script import default_generator, get_scenario
from repro.sweeps import (
    CONTRACT_VERSION,
    CampaignSpec,
    ItemFailure,
    ResultCache,
    SweepFailure,
    SweepReducer,
    SweepRow,
    build_cells,
    cell_key,
    run_campaign,
)
from repro.sweeps import executor as executor_mod
from repro.sweeps.manifest import CampaignManifest, CellRecord
from repro.sweeps.worker import run_shard

SPEC = ScenarioSpec(scenario=get_scenario("calm_to_rush"),
                    policy="ads_tile", seed=3)

CAMPAIGN_KW = dict(
    name="t", n_scenarios=2, policies=("ads_tile", "tp_driven"),
    scenario_duration_s=0.4, seed=5,
)


# ---------------------------------------------------------------------------
# cell keys
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("change", [
    {"seed": 99},
    {"policy": "tp_driven"},
    {"replan": False},
    {"replan_mode": "predictive"},
    {"target_miss": 0.05},
    {"tiles": 256},
    {"load_factor": 1.2},
    {"drop_policy": "hard"},
    {"duration_s": 0.9},
    {"record": True},
    {"scenario": get_scenario("commute")},
])
def test_cell_key_moves_with_row_relevant_fields(change):
    assert cell_key(dataclasses.replace(SPEC, **change)) != cell_key(SPEC)


def test_cell_key_stable_under_recompute_and_derived_fields():
    base = cell_key(SPEC)
    assert cell_key(SPEC) == base
    # attached portfolio and mode_defs are derived, not row inputs
    from repro.scenarios.modes import get_mode
    from repro.scenarios.runner import compile_portfolio

    derived = dataclasses.replace(
        SPEC,
        portfolio=compile_portfolio(SPEC),
        mode_defs={m: get_mode(m) for m in SPEC.scenario.modes()},
    )
    assert cell_key(derived) == base


def test_cell_key_backend_equivalence_classes():
    # scalar/lockstep/auto are bit-identical: one cache class
    exact = {cell_key(SPEC, backend=b) for b in ("auto", "scalar", "lockstep")}
    assert len(exact) == 1
    # soa is distributional: its own class
    assert cell_key(SPEC, backend="soa") not in exact
    with pytest.raises(ValueError):
        cell_key(SPEC, backend="warp")


def test_cell_key_moves_with_contract_version(monkeypatch):
    from repro.sweeps import cellkey as ck

    base = cell_key(SPEC)
    monkeypatch.setattr(ck, "CONTRACT_VERSION", CONTRACT_VERSION + 1)
    assert cell_key(SPEC) != base


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------
def test_backend_registry_metadata():
    assert set(SWEEP_BACKENDS.names()) == {"scalar", "lockstep", "soa"}
    assert "soa" in SWEEP_BACKENDS
    assert SWEEP_BACKENDS["scalar"].kind == "exact"
    assert SWEEP_BACKENDS["lockstep"].kind == "exact"
    assert SWEEP_BACKENDS["soa"].kind == "distributional"
    # exact backends support every spec; the SoA probe names its reason
    assert SWEEP_BACKENDS["lockstep"].supports(SPEC)[0]
    ok, why = SWEEP_BACKENDS["soa"].supports(
        dataclasses.replace(SPEC, replan_mode="predictive")
    )
    assert not ok and why


# ---------------------------------------------------------------------------
# run() entry point
# ---------------------------------------------------------------------------
def test_run_validations():
    with pytest.raises(ValueError, match="seeds"):
        run([SPEC, SPEC], seeds=[0, 1])
    with pytest.raises(ValueError, match="trace"):
        run(SPEC, seeds=[0, 1], trace=object())
    with pytest.raises(ValueError, match="backend"):
        run(SPEC, backend="warp")


def test_removed_shims_stay_gone():
    """The one-release deprecation window for the four historical entry
    points is over; the names must not quietly come back."""
    import repro.scenarios as scenarios
    import repro.scenarios.runner as runner

    for name in ("run_scenario", "run_scenario_batch",
                 "run_scenario_soa", "run_scenario_group"):
        assert not hasattr(runner, name), name
        assert not hasattr(scenarios, name), name
        assert name not in runner.__all__
        assert name not in scenarios.__all__

    # the run() call shapes the shims delegated to remain bit-identical
    [r_single] = run(SPEC)
    fan = run(SPEC, seeds=[3])
    specs = [SPEC, dataclasses.replace(SPEC, policy="tp_driven")]
    group = run(specs, backend="lockstep")
    assert reports_identical(r_single, fan[0])
    assert reports_identical(r_single, group[0])


# ---------------------------------------------------------------------------
# typed rows + streaming reducer
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def sweep_rows():
    return sweep(2, policies=("ads_tile", "tp_driven"),
                 duration_s=0.4, seed=5, jobs=1, record=True)


def test_sweep_row_dict_shape_and_round_trip(sweep_rows):
    [r] = run(SPEC)
    row = SweepRow.from_report(SPEC, r)
    legacy = summarize(SPEC, r)
    assert row.to_dict() == legacy
    assert list(row.to_dict()) == list(legacy)          # field order too
    assert SweepRow.from_dict(row.to_dict()).to_dict() == legacy
    for swept in sweep_rows:
        assert SweepRow.from_dict(swept).to_dict() == swept


def test_reducer_streaming_equals_batch_aggregate(sweep_rows):
    red = SweepReducer()
    for row in sweep_rows:
        red.update(row)
    assert red.result() == aggregate_sweep(sweep_rows)


# ---------------------------------------------------------------------------
# campaigns: cache hits, manifest resume, failure capture
# ---------------------------------------------------------------------------
def test_campaign_repeat_is_all_cache_hits(tmp_path):
    cache = tmp_path / "cache"
    first = run_campaign(CampaignSpec(**CAMPAIGN_KW),
                         cache_dir=cache, jobs=1)
    assert (first.n_cells, first.n_executed, first.n_cached) == (4, 4, 0)
    again = run_campaign(CampaignSpec(**CAMPAIGN_KW),
                         cache_dir=cache, jobs=1)
    assert (again.n_executed, again.n_cached) == (0, 4)
    assert again.rows == first.rows
    assert again.aggregate == first.aggregate
    # the campaign is sweep()'s durable form: same rows as the direct
    # process-pool sweep with the same arguments
    direct = sweep(CAMPAIGN_KW["n_scenarios"],
                   policies=CAMPAIGN_KW["policies"],
                   duration_s=CAMPAIGN_KW["scenario_duration_s"],
                   seed=CAMPAIGN_KW["seed"], jobs=1)
    assert first.rows == direct


def test_interrupted_campaign_resumes_row_for_row(tmp_path):
    ref = run_campaign(CampaignSpec(**CAMPAIGN_KW),
                       cache_dir=tmp_path / "ref", jobs=1)

    cache = tmp_path / "cache"
    manifest = tmp_path / "manifest.json"
    spec = CampaignSpec(**CAMPAIGN_KW)
    cells = build_cells(spec)
    CampaignManifest(
        campaign=spec.to_dict(),
        cells=[
            CellRecord(index=c.index, key=c.key,
                       scenario_index=c.scenario_index,
                       policy=str(c.spec.policy), seed=int(c.spec.seed),
                       backend=c.backend_class)
            for c in cells
        ],
        cache_dir=str(cache),
    ).save(manifest)
    # simulate an interruption: one scenario group executes, then stop
    report = run_shard(manifest, cache, max_groups=1)
    assert 0 < report["n_executed"] < 4

    resumed = run_campaign(str(manifest), jobs=1)
    assert resumed.n_cached == report["n_executed"]
    assert resumed.n_executed == 4 - report["n_executed"]
    assert resumed.rows == ref.rows


def test_failed_cells_are_captured_not_fatal(tmp_path):
    cache = tmp_path / "cache"
    bad = CampaignSpec(**{**CAMPAIGN_KW,
                          "policies": ("ads_tile", "no_such_policy")})
    with pytest.raises(SweepFailure) as ei:
        run_campaign(bad, cache_dir=cache,
                     manifest_path=tmp_path / "m.json", jobs=1)
    result = ei.value.result
    assert result.n_failed == 2 and len(ei.value.failed_keys) == 2
    assert result.n_executed == 2          # good cells ran and persisted
    manifest = CampaignManifest.load(tmp_path / "m.json")
    assert sorted(manifest.failed_keys()) == sorted(ei.value.failed_keys)
    # the completed cells are in the cache: the good-policy campaign
    # over the same scenarios re-executes nothing
    good = run_campaign(
        CampaignSpec(**{**CAMPAIGN_KW, "policies": ("ads_tile",)}),
        cache_dir=cache, jobs=1,
    )
    assert (good.n_executed, good.n_cached) == (0, 2)
    # allow_failures returns the partial result instead of raising
    partial = run_campaign(bad, cache_dir=cache, jobs=1,
                           allow_failures=True)
    assert partial.n_failed == 2 and len(partial.rows) == 2


def test_campaign_streaming_matches_kept_rows(tmp_path):
    spec = CampaignSpec(**CAMPAIGN_KW)
    kept = run_campaign(spec, cache_dir=tmp_path / "c", jobs=1)
    streamed = run_campaign(spec, cache_dir=tmp_path / "c", jobs=1,
                            keep_rows=False)
    assert streamed.rows is None
    assert streamed.aggregate == kept.aggregate


def test_campaign_spec_json_round_trip():
    gen = default_generator()
    spec = CampaignSpec(**CAMPAIGN_KW, generator=gen,
                        spec_kw={"record": True, "tiles": 256})
    d = json.loads(json.dumps(spec.to_dict()))
    back = CampaignSpec.from_dict(d)
    assert back.policies == spec.policies
    assert back.spec_kw == spec.spec_kw
    assert back.generator.transitions == gen.transitions
    assert back.to_dict() == spec.to_dict()


def test_manifest_round_trip_and_version_guard(tmp_path):
    spec = CampaignSpec(**CAMPAIGN_KW)
    res = run_campaign(spec, cache_dir=tmp_path / "c",
                       manifest_path=tmp_path / "m.json", jobs=1)
    loaded = CampaignManifest.load(tmp_path / "m.json")
    assert loaded.counts() == res.manifest.counts()
    assert [c.key for c in loaded.cells] == [c.key for c in res.manifest.cells]
    d = json.loads((tmp_path / "m.json").read_text())
    assert CampaignManifest.is_manifest(d)
    d["version"] = 99
    (tmp_path / "m.json").write_text(json.dumps(d))
    with pytest.raises(ValueError, match="version"):
        CampaignManifest.load(tmp_path / "m.json")


def test_cache_treats_corruption_as_miss(tmp_path):
    cache = ResultCache(tmp_path)
    cache.put("ab" * 32, {"x": 1.5})
    assert cache.get("ab" * 32) == {"x": 1.5}
    path = tmp_path / ("ab" * 32)[:2] / (("ab" * 32) + ".json")
    path.write_text("{truncated")
    assert cache.get("ab" * 32) is None
    assert cache.get("cd" * 32) is None


# ---------------------------------------------------------------------------
# parallel_map failure semantics (the satellite bugfix)
# ---------------------------------------------------------------------------
def _square(x):
    return x * x


def _boom(x):
    if x == 2:
        raise ValueError("boom on 2")
    return x


def test_parallel_map_return_errors_in_place():
    out = parallel_map(_boom, [1, 2, 3], jobs=1, return_errors=True)
    assert out[0] == 1 and out[2] == 3
    assert isinstance(out[1], ItemFailure)
    assert "boom on 2" in out[1].error


def test_parallel_map_reraises_after_full_pass():
    with pytest.raises(ValueError, match="boom on 2"):
        parallel_map(_boom, [1, 2, 3], jobs=1)
    assert parallel_map(_square, [1, 2, 3], jobs=1) == [1, 4, 9]


# ---------------------------------------------------------------------------
# worker processes stay off the accelerator
# ---------------------------------------------------------------------------
def _worker_env(_):
    return os.getpid(), os.environ.get("JAX_PLATFORMS")


def test_pool_workers_run_on_cpu(monkeypatch):
    """A chip belongs to one process, and the caller may hold it: pool
    workers start with JAX_PLATFORMS=cpu whatever the caller's
    environment says, and the caller's environment is left as it was."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    out = executor_mod.LocalPoolExecutor(2).map(_worker_env, [0, 1])
    assert all(pid != os.getpid() for pid, _p in out)
    assert [plat for _pid, plat in out] == ["cpu", "cpu"]
    assert "JAX_PLATFORMS" not in os.environ


def test_soa_campaign_runs_in_calling_process(monkeypatch, tmp_path):
    """SoA cells need the caller's accelerator: a campaign asked for
    two pool workers runs them here, not in CPU-only workers."""
    from repro.scenarios import runner

    pids = []
    real = runner._run_soa

    def spy(spec, seeds, options=None):
        pids.append(os.getpid())
        return real(spec, seeds, options)

    monkeypatch.setattr(runner, "_run_soa", spy)
    spec = CampaignSpec(**{**CAMPAIGN_KW, "policies": ("cyc",),
                           "backend": "soa"})
    res = run_campaign(spec, cache_dir=tmp_path / "c", jobs=2)
    assert res.n_executed == 2
    assert pids == [os.getpid()] * 2


def test_shard_workers_run_on_cpu(monkeypatch, tmp_path):
    seen = []

    class _Proc:
        returncode = 0

        def communicate(self, timeout=None):
            return "", ""

    def fake_popen(cmd, **kw):
        seen.append(kw["env"])
        return _Proc()

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(executor_mod.subprocess, "Popen", fake_popen)
    executor_mod.SubprocessShardExecutor(num_shards=2).run_manifest(
        tmp_path / "m.json", tmp_path / "cache"
    )
    assert [env["JAX_PLATFORMS"] for env in seen] == ["cpu", "cpu"]
    assert os.environ["JAX_PLATFORMS"] == "tpu"
