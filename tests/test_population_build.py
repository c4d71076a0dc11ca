"""The population build: one program, placed where the sweep reads it.

``stack_population`` builds a population's stacked parameters and decay
registers in one jitted program; it must return exactly what stacking
each leaf on its own returns. ``stack_population_sharded`` runs the same
build, pads the candidate axis to the shard count and places it on the
mesh once. ``eval_int_population`` places the rasters once per call too
and cuts each batch out of them on the device; its accuracies and event
statistics must equal serial ``eval_int`` and the per-batch path it
replaced, which gathered, transposed and uploaded every batch on the
host. The mesh cases run on four forced host devices in a fresh
interpreter.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend, shard
from repro.core.network import NetworkConfig, init_float_params, quantize_params
from repro.core.snn_layer import IntLayerParams, LayerConfig, NeuronModel, Topology
from repro.data.snn_datasets import mnist_like
from repro.snn import train

PRECISIONS = [(b, r, l) for b in (4, 6, 8, 12) for r in (4, 8) for l in (3, 8)] * 2


def _net(topology):
    return NetworkConfig(
        layers=(
            LayerConfig(n_in=48, n_out=12, neuron=NeuronModel.LIF, w_bits=6, u_bits=16,
                        topology=topology, beta=0.9),
            LayerConfig(n_in=12, n_out=10, neuron=NeuronModel.LIF, w_bits=6, u_bits=16,
                        beta=0.77),
        ),
        n_steps=4,
    )  # fmt: skip


def _population(topology, n):
    net = _net(topology)
    params = init_float_params(jax.random.PRNGKey(0), net)
    cands = [
        net.replace_precisions(w_bits=b, w_rec_bits=r, leak_bits=l) for b, r, l in PRECISIONS[:n]
    ]
    return cands, [quantize_params(c, params)[0] for c in cands]


def _stack_per_leaf(nets, qparams_list):
    """The build as it was: one ``jnp.stack`` per leaf, registers converted apart."""
    stacked = [
        IntLayerParams(
            w_ff=jnp.stack([qp[l].w_ff for qp in qparams_list]),
            w_rec=jnp.stack([qp[l].w_rec for qp in qparams_list]),
            theta_q=jnp.stack([qp[l].theta_q for qp in qparams_list]),
        )
        for l in range(len(nets[0].layers))
    ]
    beta = jnp.asarray(
        [[c.beta_code().decay_rate_register for c in n.layers] for n in nets], jnp.int32
    )
    alpha = jnp.asarray(
        [[c.alpha_code().decay_rate_register for c in n.layers] for n in nets], jnp.int32
    )
    return stacked, beta, alpha


@pytest.mark.parametrize("n", [1, 3, 32])
@pytest.mark.parametrize("topology", [Topology.FF, Topology.ATA_F, Topology.ATA_T])
def test_one_program_build_equals_per_leaf_stack(topology, n):
    cands, qps = _population(topology, n)
    got = backend.stack_population(cands, qps)
    want = _stack_per_leaf(cands, qps)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert g.shape[0] == n
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _dataset():
    """20 rasters over the nets' 48 inputs: batches of 8 leave a ragged 4."""
    ds = mnist_like(n=20, T=4, seed=5)
    return type(ds)(ds.spikes[..., :48], ds.labels, ds.n_classes, ds.name)


def _per_batch_sweep(net, cands, qps, ds, batch_size, mesh=None):
    """The sweep as it was: each batch gathered, transposed and uploaded on the host.

    Returns accuracies ``[P]``, layer events ``[P, T, L]`` and input events ``[T]``.
    """
    dmesh = shard.resolve_mesh(mesh)
    stacked, beta, alpha = shard.stack_population_sharded(cands, qps, dmesh)
    n_cand = len(cands)
    correct, total, layer_ev, in_ev = np.zeros(n_cand, np.int64), 0, 0.0, 0.0
    for spikes, labels in ds.batches(batch_size):
        spikes = jnp.asarray(spikes)
        if dmesh is None:
            out = train._population_fwd(net, stacked, beta, alpha, spikes)
        else:
            counts, emitted = shard.run_int_population_sharded(
                net, stacked, beta, alpha, spikes, dmesh, return_events=True
            )
            iev = jnp.mean(jnp.sum(spikes != 0, axis=-1), axis=-1)
            out = jnp.argmax(counts, axis=-1), jnp.mean(emitted, axis=-1), iev
        preds, evs, iev = (np.asarray(a) for a in out)
        n = len(labels)
        correct += (preds[:n_cand] == labels[None, :]).sum(axis=1)
        total += n
        layer_ev, in_ev = layer_ev + evs[:n_cand] * n, in_ev + iev * n
    return correct / total, layer_ev / total, in_ev / total


def _same_as_serial_and_per_batch(net, cands, qps, ds, batch_size, mesh=None):
    """Per-candidate equality of the placed sweep with serial ``eval_int`` and the old path."""
    accs, stats = train.eval_int_population(
        net, cands, qps, ds, batch_size=batch_size, return_stats=True, mesh=mesh
    )
    old_accs, old_layer, old_in = _per_batch_sweep(net, cands, qps, ds, batch_size, mesh)
    np.testing.assert_array_equal(accs, old_accs)
    for j, (c, q) in enumerate(zip(cands, qps)):
        acc, st = train.eval_int(c, q, ds, batch_size=batch_size, return_stats=True)
        assert accs[j] == acc
        got = np.stack(stats[j]["layer_events_per_step"], axis=1)  # [T, L]
        np.testing.assert_array_equal(got, old_layer[j])
        np.testing.assert_array_equal(got, np.stack(st["layer_events_per_step"], axis=1))
        np.testing.assert_array_equal(stats[j]["input_events_per_step"], old_in)
        np.testing.assert_array_equal(stats[j]["input_events_per_step"], st["input_events_per_step"])
    return accs


@pytest.mark.parametrize("topology", [Topology.FF, Topology.ATA_F, Topology.ATA_T])
def test_placed_sweep_with_a_ragged_batch_equals_serial_and_per_batch(topology):
    cands, qps = _population(topology, 5)
    accs = _same_as_serial_and_per_batch(cands[0], cands, qps, _dataset(), batch_size=8)
    assert len(accs) == 5


def test_one_batch_larger_than_the_set_takes_the_whole_set():
    cands, qps = _population(Topology.ATA_F, 3)
    _same_as_serial_and_per_batch(cands[0], cands, qps, _dataset(), batch_size=64)


_MESH_PROG = """
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {tests!r})
import jax, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from test_population_build import _dataset, _population, _same_as_serial_and_per_batch
from repro.core import shard
from repro.core.snn_layer import Topology
from repro.snn.train import eval_int, eval_int_population

assert len(jax.devices()) == 4
dmesh = shard.make_mesh(4)
cands, qps = _population(Topology.ATA_T, 5)
stacked, beta, alpha = shard.stack_population_sharded(cands, qps, dmesh)
want = NamedSharding(dmesh.mesh, P(dmesh.axis))
placed = [
    (list(a.shape), a.sharding.is_equivalent_to(want, a.ndim), a.size)
    for a in jax.tree.leaves((stacked, beta, alpha))
]
tail = [bool((np.asarray(a)[5:] == np.asarray(a)[4]).all()) for a in jax.tree.leaves(stacked)]
ds = _dataset()
pop = eval_int_population(cands[0], cands, qps, ds, batch_size=8, mesh=4)
serial = [eval_int(c, q, ds, batch_size=8) for c, q in zip(cands, qps)]
# raises where the placed sweep's stats differ from serial or the per-batch path
_same_as_serial_and_per_batch(cands[0], cands, qps, ds, batch_size=8, mesh=4)
print(json.dumps({{"placed": placed, "tail": tail, "pop": list(pop), "serial": serial,
                  "stats_equal": True}}))
"""


@pytest.fixture(scope="module")
def mesh_result():
    """The four-device cases, run once in an interpreter of their own."""
    if jax.default_backend() != "cpu":
        pytest.skip("forces host devices")
    prog = _MESH_PROG.format(tests=os.path.dirname(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([p for p in sys.path if p] + [env.get("PYTHONPATH", "")])
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(prog)], capture_output=True,
                         text=True, env=env, timeout=300)  # fmt: skip
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_sharded_build_is_padded_and_placed_on_the_candidate_axis(mesh_result):
    placed = mesh_result["placed"]
    assert len(placed) == 8  # three leaves per layer, then beta and alpha
    for shape, on_axis, size in placed:
        assert shape[0] == 8  # five candidates padded to the next multiple of four
        # XLA replicates an array with no elements; it holds nothing to place
        assert on_axis or size == 0
    assert sum(on_axis for _, on_axis, _ in placed) >= 7
    assert all(mesh_result["tail"])  # the padding repeats the last candidate


def test_ragged_population_on_four_devices_scores_as_serial(mesh_result):
    assert len(mesh_result["pop"]) == 5
    assert mesh_result["pop"] == mesh_result["serial"]


def test_ragged_population_on_four_devices_stats_equal_serial_and_per_batch(mesh_result):
    # five candidates over four devices, 20 rasters in batches of 8: both axes ragged
    assert mesh_result["stats_equal"]
