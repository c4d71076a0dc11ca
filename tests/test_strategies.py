"""Conformance suite for the pluggable Flex-plorer search strategies.

Every registered strategy must honour the ``SearchStrategy`` protocol
contract: seeded determinism, complete JSON-serialisable state
(resume-from-checkpoint replays the uninterrupted trajectory exactly),
serial == population scoring, non-dominated fronts, and -- for the cost
model -- ``c_bw = 0`` reproduces pre-bottleneck-model scores bit-exactly.

The fast half of the suite runs ``run_search`` over a synthetic host-only
cost surface (no jax); the integration half drives ``explore_snn`` on a
tiny network, including the mid-search kill + resume and the redesigned
spec API / deprecation shim.
"""

import json
import pickle

import jax
import numpy as np
import pytest

from repro.core import hw_model
from repro.core.flexplorer import cost as cost_lib
from repro.core.flexplorer import strategies as S
from repro.core.network import NetworkConfig, init_float_params
from repro.core.snn_layer import LayerConfig, NeuronModel, ResetMode
from repro.data.snn_datasets import mnist_like

# ---------------------------------------------------------------------------
# Synthetic surface (host-only, fast)
# ---------------------------------------------------------------------------

KNOBS = {"a": (2, 4, 6, 8), "b": (1, 3, 5), "c": (0, 1)}


def _hw(cfg):
    return (cfg[0] + cfg[1] + cfg[2]) / 20.0


def _acc(cfg):
    return 1.0 - abs(cfg[0] - 6) / 10.0 - abs(cfg[1] - 3) / 10.0 + cfg[2] / 50.0


def _batch_acc(batch):
    return [_acc(c) for c in batch]


def _acc_cost(a):
    return 0.5 * (1.0 - a)


STRATEGY_CASES = {
    "anneal-serial": lambda: S.AnnealStrategy(
        KNOBS, S.AnnealConfig(t_start=1.0, t_min=0.05, alpha=0.6, seed=3)
    ),
    "anneal-pop": lambda: S.PopulationAnnealStrategy(
        KNOBS, S.AnnealConfig(t_start=1.0, t_min=0.05, alpha=0.6, seed=3), population=4
    ),
    "nsga2": lambda: S.NSGAStrategy(
        KNOBS, S.NSGAConfig(population=8, generations=5, seed=3)
    ),
}


@pytest.fixture(params=sorted(STRATEGY_CASES), ids=sorted(STRATEGY_CASES))
def make_strategy(request):
    return STRATEGY_CASES[request.param]


def _run(strategy, batch_acc=_batch_acc, **kw):
    return S.run_search(strategy, KNOBS, _hw, batch_acc, _acc_cost, **kw)


def test_registry_lists_both_families():
    assert set(S.available_strategies()) >= {"anneal", "nsga2"}
    assert isinstance(S.make_strategy("anneal", KNOBS), S.AnnealStrategy)
    assert isinstance(
        S.make_strategy("anneal", KNOBS, population=4), S.PopulationAnnealStrategy
    )
    assert isinstance(S.make_strategy("nsga2", KNOBS), S.NSGAStrategy)
    with pytest.raises(ValueError, match="unknown search strategy"):
        S.make_strategy("gradient-descent", KNOBS)


def test_seeded_determinism(make_strategy):
    a, b = _run(make_strategy()), _run(make_strategy())
    assert a.best == b.best and a.best_cost == b.best_cost
    assert a.evaluations == b.evaluations
    assert [t["cfg"] for t in a.trace] == [t["cfg"] for t in b.trace]
    assert a.cache == b.cache
    assert a.front == b.front


def test_resume_after_kill_equals_uninterrupted(make_strategy, tmp_path):
    from repro.checkpoint.checkpointer import Checkpointer

    full = _run(make_strategy())

    calls = {"n": 0}

    def flaky(batch):
        calls["n"] += 1
        if calls["n"] == 3:  # dies mid-schedule, after 2 completed rounds
            raise RuntimeError("killed")
        return _batch_acc(batch)

    ck = Checkpointer(tmp_path / "search")
    with pytest.raises(RuntimeError, match="killed"):
        _run(make_strategy(), batch_acc=flaky, checkpointer=ck)
    resumed = _run(make_strategy(), checkpointer=Checkpointer(tmp_path / "search"))
    assert resumed.best == full.best and resumed.best_cost == full.best_cost
    assert resumed.evaluations == full.evaluations
    assert [t["cfg"] for t in resumed.trace] == [t["cfg"] for t in full.trace]
    assert resumed.front == full.front
    # resuming a *finished* search is a no-op returning the same result
    again = _run(make_strategy(), checkpointer=Checkpointer(tmp_path / "search"))
    assert again.best == full.best and again.evaluations == full.evaluations


def test_resume_refuses_foreign_snapshot(tmp_path):
    from repro.checkpoint.checkpointer import Checkpointer

    _run(
        STRATEGY_CASES["nsga2"](),
        checkpointer=Checkpointer(tmp_path / "s"),
        max_rounds=2,
    )
    with pytest.raises(ValueError, match="refusing to resume"):
        _run(STRATEGY_CASES["anneal-serial"](), checkpointer=Checkpointer(tmp_path / "s"))


def test_state_dict_json_roundtrip(make_strategy):
    strat = make_strategy()
    partial = _run(strat, max_rounds=3)
    assert not strat.finished
    clone = make_strategy()
    clone.load_state_dict(json.loads(json.dumps(strat.state_dict())))
    assert clone.propose(partial.cache) == strat.propose(partial.cache)


def test_front_is_non_dominated(make_strategy):
    result = _run(make_strategy())
    assert result.front
    objs = [p["objectives"] for p in result.front]
    for i, a in enumerate(objs):
        for j, b in enumerate(objs):
            if i != j:
                assert not S.dominates(a, b), (a, b)
    cached_cfgs = {tuple(sorted(p["cfg"].items())) for p in result.front}
    traced = {tuple(sorted(t["cfg"].items())) for t in result.trace}
    assert cached_cfgs <= traced


def test_population_scores_match_serial():
    cfg = S.AnnealConfig(t_start=1.0, t_min=0.05, alpha=0.6, seed=0)
    serial = _run(S.AnnealStrategy(KNOBS, cfg))
    pop = _run(S.PopulationAnnealStrategy(KNOBS, cfg, population=4))
    shared = serial.cache.keys() & pop.cache.keys()
    assert shared
    for c in shared:
        assert serial.cache[c] == pop.cache[c]


def test_max_evaluations_caps_budget(make_strategy):
    capped = _run(make_strategy(), max_evaluations=6)
    assert capped.evaluations <= 6 + 8  # at most one extra round beyond the cap
    full = _run(make_strategy())
    assert capped.evaluations <= full.evaluations


def test_nsga_covers_more_of_the_front_than_it_must():
    """NSGA-II's reported front equals the true non-dominated set of its cache."""
    result = _run(STRATEGY_CASES["nsga2"]())
    objs = {c: rec.objectives for c, rec in result.cache.items()}
    true_front = {
        c
        for c in objs
        if not any(S.dominates(objs[o], objs[c]) for o in objs if o != c)
    }
    names = tuple(KNOBS)
    reported = {tuple(p["cfg"][k] for k in names) for p in result.front}
    assert reported == true_front


def test_non_dominated_sort_and_crowding():
    objs = [(0.0, 1.0), (1.0, 0.0), (0.5, 0.5), (1.0, 1.0), (2.0, 2.0)]
    fronts = S.non_dominated_sort(objs)
    assert fronts[0] == [0, 1, 2]
    assert fronts[1] == [3]
    assert fronts[2] == [4]
    crowd = S.crowding_distance(objs, fronts[0])
    assert crowd[0] == crowd[1] == float("inf")  # extremes kept
    assert np.isfinite(crowd[2])


def test_eval_record_is_legacy_tuple_plus_extras():
    rec = S.EvalRecord(0.5, 0.2, 0.1, 0.8, 0.2, metrics={"latency_s": 1e-3})
    total, hw, a_cost, accuracy, p_cost = rec
    assert (total, hw, a_cost, accuracy, p_cost) == (0.5, 0.2, 0.1, 0.8, 0.2)
    assert rec[3] == rec.accuracy == 0.8
    assert rec.objectives == (1.0 - 0.8, 0.2)
    clone = pickle.loads(pickle.dumps(rec))
    assert clone == rec and clone.objectives == rec.objectives
    assert clone.metrics == {"latency_s": 1e-3}
    assert json.dumps(rec.to_json())  # JSON-serialisable


def test_search_result_to_json_uniform_schema(make_strategy):
    out = _run(make_strategy()).to_json()
    assert set(out) >= {"strategy", "best", "best_cost", "evaluations", "front", "trace", "cache"}
    json.dumps(out)  # fully serialisable


# ---------------------------------------------------------------------------
# Bottleneck-aware cost model
# ---------------------------------------------------------------------------


def test_cost_weights_bw_constraint():
    cost_lib.CostWeights()  # defaults (c_bw = 0) stay valid
    cost_lib.CostWeights(c_lat=0.4, c_energy=0.4, c_bw=0.2)
    with pytest.raises(ValueError, match="C_BW"):
        cost_lib.CostWeights(c_lat=0.5, c_energy=0.5, c_bw=0.2)


def test_perf_cost_bit_exact_when_bw_weight_zero():
    w = cost_lib.CostWeights(c_hw=0.4, c_acc=0.4, c_perf=0.2)
    t = cost_lib.PerfTargets()
    for lat, e in [(1.1e-3, 0.12e-3), (3.7e-4, 9.1e-5), (2.2e-3, 4.4e-4)]:
        expected = w.c_perf * (w.c_lat * (lat / t.latency_s) + w.c_energy * (e / t.energy_j))
        assert cost_lib.perf_cost(lat, e, w, t) == expected
        # a non-zero congestion is inert while c_bw == 0
        assert cost_lib.perf_cost(lat, e, w, t, bw_congestion=7.0) == expected


def test_perf_cost_congestion_term():
    w = cost_lib.CostWeights(c_hw=0.4, c_acc=0.4, c_perf=0.2, c_lat=0.4, c_energy=0.4, c_bw=0.2)
    base = cost_lib.perf_cost(1.1e-3, 0.12e-3, w, bw_congestion=0.0)
    congested = cost_lib.perf_cost(1.1e-3, 0.12e-3, w, bw_congestion=0.5)
    assert congested == pytest.approx(base + w.c_perf * w.c_bw * 0.5)


def test_bandwidth_profile_anchor_uncongested():
    net = hw_model._paper_anchor_net()
    traffic = hw_model.paper_mnist_traffic()
    bw = hw_model.bandwidth_profile(net, traffic)
    assert len(bw.layer_bytes_per_image) == 2
    assert bw.total_bytes_per_image > 0
    assert bw.duration_s == pytest.approx(1.1e-3)
    # the paper's anchor design fits comfortably in a Zynq HP port
    assert bw.congestion(cost_lib.XC7Z020.mem_bw_bytes_s) == 0.0
    # a starved memory system shows fractional overshoot
    tight = bw.demand_bytes_s / 2
    assert bw.congestion(tight) == pytest.approx(1.0)
    with pytest.raises(ValueError):
        bw.congestion(0.0)


def test_design_point_carries_bandwidth_demand():
    net = hw_model._paper_anchor_net()
    traffic = hw_model.paper_mnist_traffic()
    dp = hw_model.design_point(net, traffic)
    bw = hw_model.bandwidth_profile(net, traffic)
    assert dp.bw_demand_bytes_s == pytest.approx(bw.demand_bytes_s)
    # higher precision moves strictly more bytes at the same traffic
    wide = net.replace_precisions(w_bits=16, w_rec_bits=16, leak_bits=8)
    assert (
        hw_model.bandwidth_profile(wide, traffic).demand_bytes_s > bw.demand_bytes_s
    )


# ---------------------------------------------------------------------------
# Multi-host fan-out helpers
# ---------------------------------------------------------------------------


def test_host_bounds_partition():
    from repro.core import shard as shard_lib

    assert shard_lib.host_bounds(8, index=0, count=1) == (0, 8)
    assert shard_lib.host_bounds(8, index=1, count=4) == (2, 4)
    bounds = [shard_lib.host_bounds(12, index=i, count=3) for i in range(3)]
    assert bounds == [(0, 4), (4, 8), (8, 12)]
    with pytest.raises(ValueError, match="does not divide"):
        shard_lib.host_bounds(10, index=0, count=4)
    with pytest.raises(ValueError, match="outside"):
        shard_lib.host_bounds(8, index=4, count=4)


def test_allgather_hosts_identity_and_fake_gather():
    from repro.core import shard as shard_lib

    x = np.arange(6).reshape(3, 2)
    np.testing.assert_array_equal(shard_lib.allgather_hosts(x), x)

    def fake_gather(local):  # emulates two hosts contributing rank-ordered slices
        return np.concatenate([local, local + 100], axis=0)

    out = shard_lib.allgather_hosts(x, count=2, gather=fake_gather)
    np.testing.assert_array_equal(out[:3], x)
    np.testing.assert_array_equal(out[3:], x + 100)


def test_maybe_init_distributed_noop_without_coordinator(monkeypatch):
    from repro.distributed import compat

    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    assert compat.maybe_init_distributed() is False
    assert compat.process_count() == 1
    assert compat.process_index() == 0


# ---------------------------------------------------------------------------
# explore_snn integration: NSGA-II, resume, spec API, shim, backend warning
# ---------------------------------------------------------------------------


def _tiny_setup():
    net = NetworkConfig(
        layers=(
            LayerConfig(n_in=32, n_out=16, neuron=NeuronModel.LIF,
                        reset=ResetMode.SUBTRACT, beta=0.9),
            LayerConfig(n_in=16, n_out=4, neuron=NeuronModel.LIF,
                        reset=ResetMode.SUBTRACT, beta=0.77),
        ),
        n_steps=6,
    )
    params = init_float_params(jax.random.PRNGKey(1), net)
    ds = mnist_like(n=64, T=6, seed=6)
    ds.spikes = ds.spikes[:, :, : net.n_in]
    ds.labels = ds.labels % 4
    return net, params, ds


@pytest.fixture(scope="module")
def tiny():
    return _tiny_setup()


def _space():
    from repro.core.flexplorer.explorer import SNNSearchSpace

    return SNNSearchSpace(ff_bits=(4, 6, 8), leak_bits=(3, 8))


def test_explore_snn_nsga_front_and_score_parity(tiny):
    from repro.core.flexplorer.explorer import EvalSpec, SearchSpec, explore_snn

    net, params, ds = tiny
    ev = EvalSpec(batch=32)
    nsga = explore_snn(
        net, params, ds,
        search=SearchSpec(
            space=_space(), strategy="nsga2",
            config=S.NSGAConfig(population=6, generations=3, seed=0),
        ),
        evaluate=ev,
    )
    assert nsga.search.strategy == "nsga2"
    assert nsga.search.front
    objs = [p["objectives"] for p in nsga.search.front]
    for i, a in enumerate(objs):
        for j, b in enumerate(objs):
            if i != j:
                assert not S.dominates(a, b)
    # scoring is strategy-independent: shared candidates match the annealer's
    anneal = explore_snn(
        net, params, ds,
        search=SearchSpec(
            space=_space(),
            config=S.AnnealConfig(t_start=1.0, t_min=0.2, alpha=0.5, seed=0),
            population=4,
        ),
        evaluate=ev,
    )
    shared = nsga.search.cache.keys() & anneal.search.cache.keys()
    assert shared
    for c in shared:
        assert nsga.search.cache[c][3] == anneal.search.cache[c][3]


def test_explore_snn_kill_and_resume_identical_front(tiny, tmp_path, monkeypatch):
    from repro.core.flexplorer import explorer as explorer_mod
    from repro.core.flexplorer.explorer import EvalSpec, SearchSpec, explore_snn

    from repro.core.flexplorer.explorer import SNNSearchSpace

    net, params, ds = tiny
    # space large enough (15 cfgs) that the search needs several sweep calls
    spec = dict(
        space=SNNSearchSpace(ff_bits=(2, 3, 4, 6, 8), leak_bits=(2, 3, 8)),
        strategy="nsga2",
        config=S.NSGAConfig(population=8, generations=3, seed=0),
    )
    ev = EvalSpec(batch=32)
    full = explore_snn(
        net, params, ds,
        search=SearchSpec(**spec, checkpoint_dir=str(tmp_path / "full")),
        evaluate=ev,
    )

    real_sweep = explorer_mod.eval_int_population
    calls = {"n": 0}

    def dies_mid_generation(*args, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("killed mid-generation")
        return real_sweep(*args, **kw)

    monkeypatch.setattr(explorer_mod, "eval_int_population", dies_mid_generation)
    with pytest.raises(RuntimeError, match="killed"):
        explore_snn(
            net, params, ds,
            search=SearchSpec(**spec, checkpoint_dir=str(tmp_path / "killed")),
            evaluate=ev,
        )
    assert calls["n"] == 2  # the kill really happened mid-search
    monkeypatch.setattr(explorer_mod, "eval_int_population", real_sweep)
    resumed = explore_snn(
        net, params, ds,
        search=SearchSpec(**spec, checkpoint_dir=str(tmp_path / "killed")),
        evaluate=ev,
    )
    assert resumed.search.front == full.search.front
    assert resumed.search.best == full.search.best
    assert [t["cfg"] for t in resumed.search.trace] == [t["cfg"] for t in full.search.trace]


def test_explore_snn_rejects_unknown_kwargs(tiny):
    from repro.core.flexplorer.explorer import explore_snn

    net, params, ds = tiny
    with pytest.raises(TypeError, match="unexpected keyword"):
        explore_snn(net, params, ds, annealing_config=None)


def test_population_backend_warning_compares_by_value(tiny):
    import warnings as _w

    from repro.core.backend import FusedBackend, ReferenceBackend
    from repro.core.flexplorer.explorer import EvalSpec, SearchSpec, explore_snn

    net, params, ds = tiny
    cfg = S.AnnealConfig(t_start=1.0, t_min=0.3, alpha=0.5, seed=0)
    spec = SearchSpec(space=_space(), config=cfg, population=2)
    # an explicit ReferenceBackend() instance is config-identical to the
    # default: no "backend is ignored" warning (regression: the old check
    # used `type is`, which an instance passed through a wrapper defeated)
    assert ReferenceBackend() == ReferenceBackend()
    with _w.catch_warnings(record=True) as caught:
        _w.simplefilter("always")
        explore_snn(
            net, params, ds, search=spec, evaluate=EvalSpec(batch=32, backend=ReferenceBackend())
        )
    assert not [w for w in caught if "ignored" in str(w.message)]
    with _w.catch_warnings(record=True) as caught:
        _w.simplefilter("always")
        explore_snn(
            net, params, ds, search=spec, evaluate=EvalSpec(batch=32, backend=FusedBackend())
        )
    assert [w for w in caught if "ignored" in str(w.message)]


def test_exploration_result_to_json(tiny):
    from repro.core.flexplorer.explorer import EvalSpec, SearchSpec, explore_snn

    net, params, ds = tiny
    res = explore_snn(
        net, params, ds,
        search=SearchSpec(space=_space(), config=S.AnnealConfig(t_min=0.3, alpha=0.5)),
        evaluate=EvalSpec(batch=32),
    )
    out = res.to_json()
    json.dumps(out)
    assert out["strategy"] == "anneal"
    assert out["weights"]["c_bw"] == 0.0
    assert out["explored_front"]
