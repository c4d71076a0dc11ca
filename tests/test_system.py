"""End-to-end behaviour tests: the paper's full pipeline at smoke scale.

1. Train an SNN with surrogate-gradient BPTT on the synthetic MNIST stand-in,
2. quantize with Flex-plorer's bit-exact path and check accuracy carries over,
3. run the simulated-annealing DSE and check it returns a valid config,
4. run the fault-tolerant LM training loop with an injected failure,
5. serve a reduced LM with continuous batching (+ quantized weights).
"""


import jax
import numpy as np
import pytest

from repro.core.flexplorer import cost as cost_lib
from repro.core.flexplorer import strategies as strategies_lib
from repro.core.flexplorer.explorer import SearchSpec, SNNSearchSpace, explore_snn
from repro.core.network import NetworkConfig, quantize_params
from repro.core.snn_layer import LayerConfig
from repro.data.snn_datasets import dvs_like, mnist_like, shd_like
from repro.snn.train import eval_int, train_snn


@pytest.fixture(scope="module")
def trained_mnist():
    ds = mnist_like(n=1536, T=20, seed=0)
    train, test = ds.split()
    net = NetworkConfig(
        layers=(
            LayerConfig(n_in=256, n_out=128, w_bits=6, u_bits=16),
            LayerConfig(n_in=128, n_out=10, w_bits=6, u_bits=16),
        ),
        n_steps=20,
        name="mnist-smoke",
    )
    result = train_snn(net, train, epochs=6, batch_size=128, lr=2e-3, eval_ds=None)
    return net, result, test


def test_snn_learns_and_quantized_accuracy_holds(trained_mnist):
    net, result, test = trained_mnist
    assert result.history[-1]["train_acc"] > result.history[0]["train_acc"]
    qparams, scales = quantize_params(net, result.params)
    acc, stats = eval_int(net, qparams, test, return_stats=True)
    assert acc > 0.6, f"quantized accuracy too low: {acc}"
    assert len(stats["layer_events_per_step"]) == 2


def test_flexplorer_dse_returns_valid_config(trained_mnist):
    net, result, test = trained_mnist
    res = explore_snn(
        net,
        result.params,
        test,
        search=SearchSpec(
            space=SNNSearchSpace(ff_bits=(4, 6, 8), leak_bits=(3, 8)),
            config=strategies_lib.AnnealConfig(t_start=0.5, t_min=0.05, alpha=0.5, eval_divisor=3, seed=1),
        ),
    )
    report = res.report()
    assert report["chosen"]["ff_bits"] in (4, 6, 8)
    assert report["chosen"]["leak_bits"] in (3, 8)
    assert report["evaluations"] <= 6  # space size bounds the cache
    assert report["bram"] >= 1
    # every probed candidate recorded for the Fig.-11 style plot
    assert len(res.search.trace) == report["evaluations"]


def test_annealer_finds_global_optimum_on_known_surface():
    knobs = {"a": [1, 2, 3, 4], "b": [10, 20, 30]}
    target = (3, 20)
    hw = lambda cfg: 0.05 * abs(cfg[0] - target[0])
    acc = lambda cfg: 1.0 - 0.1 * abs(cfg[1] - target[1]) / 10.0
    cfg = strategies_lib.AnnealConfig(t_start=1.0, t_min=1e-3, alpha=0.7, eval_divisor=1, seed=0)
    res = strategies_lib.run_search(
        strategies_lib.AnnealStrategy(knobs, cfg),
        knobs,
        hw,
        batch_acc_fn=lambda batch: [float(acc(c)) for c in batch],
        acc_cost_fn=lambda a: 0.5 * (1 - a),
    )
    assert res.best == target


def test_other_benchmarks_generate():
    shd = shd_like(n=32, T=10)
    dvs = dvs_like(n=32, T=10)
    assert shd.spikes.shape == (32, 10, 140) and shd.n_classes == 20
    assert dvs.spikes.shape == (32, 10, 256) and dvs.n_classes == 11
    assert 0.005 < shd.spikes.mean() < 0.5
    assert 0.005 < dvs.spikes.mean() < 0.5


def test_cost_weights_validate():
    with pytest.raises(ValueError):
        cost_lib.CostWeights(c_hw=0.7, c_acc=0.5)
    with pytest.raises(ValueError):
        cost_lib.CostWeights(c_lut=0.5, c_ff=0.5, c_bram=0.5)


# ---------------------------------------------------------------------------
# LM train loop with fault injection + serving
# ---------------------------------------------------------------------------


def test_train_loop_survives_injected_failure(tmp_path):
    from repro.launch.mesh import make_host_mesh
    from repro.train.loop import TrainLoop

    loop = TrainLoop(
        arch_name="stablelm-1.6b",
        seq_len=32,
        global_batch=4,
        mesh=make_host_mesh(),
        run_dir=str(tmp_path),
        ckpt_every=5,
        log_every=5,
        fail_at_step=12,
    )
    out = loop.run(total_steps=20)
    assert out["failures"] == 1
    assert out["final_step"] == 20
    assert out["final_loss"] < out["first_loss"]
    events = [l for l in open(out["metrics_path"])]
    assert any('"failure"' in l for l in events)
    assert any('"restored"' in l for l in events)


def test_serve_engine_continuous_batching_matches_greedy():
    from repro.models.registry import get_arch
    from repro.serve.engine import Request, ServeEngine

    arch = get_arch("stablelm-1.6b")
    params = arch.init_params(jax.random.PRNGKey(0), arch.reduced_config)
    eng = ServeEngine(arch, params, max_batch=2, max_len=64)
    reqs = [
        Request(uid=i, prompt=np.asarray([3, 17, 29]), max_new_tokens=5) for i in range(4)
    ]
    done = eng.run(list(reqs))
    assert len(done) == 4
    assert all(len(r.generated) == 5 for r in done)
    # identical prompts must produce identical greedy outputs regardless of
    # which slot/batch wave served them (continuous-batching correctness)
    gens = {tuple(r.generated) for r in done}
    assert len(gens) == 1


def test_serve_engine_quantized_weights():
    from repro.core.precision import PrecisionPolicy
    from repro.models.registry import get_arch
    from repro.serve.engine import Request, ServeEngine

    arch = get_arch("stablelm-1.6b")
    params = arch.init_params(jax.random.PRNGKey(0), arch.reduced_config)
    policy = PrecisionPolicy(rules=((r"(wq|wk|wv|wo|w_gate|w_up|w_down)$", 8),))
    eng = ServeEngine(arch, params, max_batch=2, max_len=64, quant=policy)
    done = eng.run([Request(uid=0, prompt=np.asarray([5, 11]), max_new_tokens=4)])
    assert len(done[0].generated) == 4
