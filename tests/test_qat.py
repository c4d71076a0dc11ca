"""QAT contracts: STE fake-quant == deployment quantization, bit for bit.

The whole value of ``repro.snn.qat`` is that nothing new exists at inference
time: a QAT-trained network deploys through the unchanged ``quantize_params``
-> ``eval_int`` path and scores *exactly* what training measured.  These
tests pin that equivalence at its three levels -- parameter rounding, full
forward logits (every neuron model x topology x reset mode), and the
train/eval entry points -- plus the refinement loop's never-worse guarantee.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.network import (
    NetworkConfig,
    init_float_params,
    quantize_params,
    run_int,
)
from repro.core.snn_layer import LayerConfig, NeuronModel, ResetMode, Topology
from repro.data.snn_datasets import mnist_like
from repro.snn.qat import (
    PrecisionConfig,
    eval_qat,
    fake_quant_layer,
    refine_candidates,
    run_qat,
)
from repro.snn.surrogate import fast_sigmoid
from repro.snn.train import eval_int, train_snn

SPIKE_FN = fast_sigmoid(25.0)


def _net(neuron, topo, reset, w_bits=3, leak_bits=4):
    thr = 2.5 if neuron == NeuronModel.SYNAPTIC else 1.0
    mk = lambda n_in, n_out, wb: LayerConfig(
        n_in=n_in,
        n_out=n_out,
        neuron=neuron,
        topology=topo,
        reset=reset,
        w_bits=wb,
        leak_bits=leak_bits,
        u_bits=12,
        threshold=thr,
    )
    return NetworkConfig(
        layers=(mk(24, 16, w_bits), mk(16, 5, w_bits + 1)), n_steps=10, name="qat-test"
    )


def _spikes(rng, T=10, batch=6, n_in=24, density=0.3):
    return jnp.asarray((rng.random((T, batch, n_in)) < density).astype(np.uint8))


@pytest.mark.parametrize("topo", [Topology.FF, Topology.ATA_F, Topology.ATA_T])
def test_fake_quant_equals_quantize_params_rounding(topo):
    net = _net(NeuronModel.LIF, topo, ResetMode.SUBTRACT)
    params = init_float_params(jax.random.PRNGKey(0), net)
    qparams, scales = quantize_params(net, params)
    for cfg, p, q, s in zip(net.layers, params, qparams, scales):
        fq = fake_quant_layer(cfg, p)
        assert float(fq.scale) == s
        assert np.array_equal(np.asarray(fq.w_ff), np.asarray(q.w_ff, np.float32))
        assert np.array_equal(np.asarray(fq.theta_q), np.asarray(q.theta_q, np.float32))
        if topo != Topology.FF:
            assert np.array_equal(np.asarray(fq.w_rec), np.asarray(q.w_rec, np.float32))


@pytest.mark.parametrize("neuron", list(NeuronModel))
@pytest.mark.parametrize("topo", list(Topology))
@pytest.mark.parametrize("reset", list(ResetMode))
def test_qat_forward_bit_exact_with_eval_int_path(neuron, topo, reset):
    """QAT logits == quantize_params -> run_int logits, for every config."""
    net = _net(neuron, topo, reset)
    params = init_float_params(jax.random.PRNGKey(1), net)
    spikes = _spikes(np.random.default_rng(2))
    qparams, _ = quantize_params(net, params)
    counts_int = np.asarray(run_int(net, qparams, spikes).spike_counts)
    counts_qat = np.asarray(run_qat(net, params, spikes, SPIKE_FN).spike_counts)
    assert np.array_equal(counts_qat, np.round(counts_qat)), "QAT logits must be integer-valued"
    assert np.array_equal(counts_int, counts_qat.astype(counts_int.dtype))


def test_qat_forward_bit_exact_under_jit_and_at_aggressive_bits():
    net = _net(NeuronModel.LIF, Topology.FF, ResetMode.SUBTRACT, w_bits=2, leak_bits=2)
    params = init_float_params(jax.random.PRNGKey(3), net)
    spikes = _spikes(np.random.default_rng(4))
    qparams, _ = quantize_params(net, params)
    counts_int = np.asarray(run_int(net, qparams, spikes).spike_counts)
    fwd = jax.jit(lambda p, s: run_qat(net, p, s, SPIKE_FN).spike_counts)
    counts_qat = np.asarray(fwd(params, spikes))
    assert np.array_equal(counts_int, counts_qat.astype(counts_int.dtype))


def test_qat_gradients_flow_to_every_parameter():
    net = _net(NeuronModel.LIF, Topology.ATA_T, ResetMode.SUBTRACT)
    params = init_float_params(jax.random.PRNGKey(5), net)
    spikes = _spikes(np.random.default_rng(6))
    labels = jnp.asarray(np.random.default_rng(7).integers(0, 5, 6))

    def loss(params):
        counts = run_qat(net, params, spikes, SPIKE_FN).spike_counts
        logp = jax.nn.log_softmax(counts)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))

    grads = jax.grad(loss)(params)
    for g, name in [(grads[0].w_ff, "w_ff.0"), (grads[0].w_rec, "w_rec.0"), (grads[1].w_ff, "w_ff.1")]:
        total = float(jnp.sum(jnp.abs(g)))
        assert np.isfinite(total) and total > 0, f"no gradient reached {name}"


def test_precision_config_apply():
    net = _net(NeuronModel.LIF, Topology.ATA_T, ResetMode.SUBTRACT)
    q = PrecisionConfig(w_bits=2, leak_bits=3).apply(net)
    assert all(lc.w_bits == 2 and lc.leak_bits == 3 for lc in q.layers)
    # None keeps the existing knob (w_rec_bits here)
    assert [lc.w_rec_bits for lc in q.layers] == [lc.w_rec_bits for lc in net.layers]
    assert [lc.n_out for lc in q.layers] == [lc.n_out for lc in net.layers]


@pytest.fixture(scope="module")
def tiny_trained():
    ds = mnist_like(n=256, T=10, seed=11)
    train, test = ds.split()
    net = NetworkConfig(
        layers=(
            LayerConfig(n_in=256, n_out=32, w_bits=6, u_bits=16),
            LayerConfig(n_in=32, n_out=10, w_bits=6, u_bits=16),
        ),
        n_steps=10,
        name="qat-tiny",
    )
    result = train_snn(net, train, epochs=2, batch_size=64)
    return net, result, train, test


def test_train_snn_qat_roundtrips_through_eval_int(tiny_trained):
    net, result, train, test = tiny_trained
    qres = train_snn(
        net,
        train,
        epochs=1,
        batch_size=64,
        lr=5e-4,
        qat=PrecisionConfig(w_bits=3),
        init_params=result.params,
    )
    assert qres.qat_net is not None
    assert all(lc.w_bits == 3 for lc in qres.qat_net.layers)
    qparams, _ = quantize_params(qres.qat_net, qres.params)
    acc_int = eval_int(qres.qat_net, qparams, test)
    acc_qat = eval_qat(qres.qat_net, qres.params, test)
    assert acc_int == acc_qat  # the parity contract, end to end


def test_refine_candidates_never_worse_than_ptq(tiny_trained):
    net, result, train, test = tiny_trained
    candidates = [
        net.replace_precisions(w_bits=2, leak_bits=3),
        net.replace_precisions(w_bits=3, leak_bits=3),
        net.replace_precisions(w_bits=4, leak_bits=8),
    ]
    rr = refine_candidates(
        net,
        candidates,
        result.params,
        train,
        test,
        epochs=1,
        batch_size=64,
        eval_batch=128,
    )
    assert len(rr.params) == len(candidates)
    assert (rr.best_acc >= rr.base_acc).all()
    # epoch 0 *is* post-training quantization: same params, same evaluator
    for k, cand in enumerate(candidates):
        ptq_qp, _ = quantize_params(cand, result.params)
        assert rr.base_acc[k] == eval_int(cand, ptq_qp, test, batch_size=128)
    # the best checkpoint really scores what it claims, through eval_int
    for k, cand in enumerate(candidates):
        qp, _ = quantize_params(cand, rr.params[k])
        assert eval_int(cand, qp, test, batch_size=128) == rr.best_acc[k]


def test_explore_snn_refine_requires_train_ds(tiny_trained):
    from repro.core.flexplorer.explorer import RefineSpec, explore_snn

    net, result, train, test = tiny_trained
    with pytest.raises(ValueError, match=r"RefineSpec\.train_ds"):
        explore_snn(net, result.params, test, refine=RefineSpec(top_k=1))
