"""Compile rehearsals for the TPU v5e chip, plus the platform-steered rules.

The chip's compiler (Mosaic for Pallas kernels, XLA:TPU for the rest) is
installed with jax and compiles for a *described* v5e topology without a
chip attached.  These tests compile the main path's kernels at its real
widths -- the 256-128-10 network over a 1024-sample, 25-step batch and the
serving engine's 32-step x 8-lane chunk, kernels alone and the engine's
whole tick -- and assert each compiled program holds its kernel
(``tpu_custom_call``), so a kernel the chip would refuse fails here
instead of on the chip.  Nothing runs; results are the parity
suites' business.

The topology is described inside a module-scoped fixture (never at import
time: only one process at a time may load the TPU library), and the
persistent compilation cache is off around these compiles (a TPU entry
written here could not be read back without a chip).

The rule tests steer ``repro.core.lowering.on_tpu`` to hold the TPU-only
decisions -- the bf16 bound on the f32 lowering, no interpret mode -- on
CPU.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import lowering
from repro.core.backend import EventBackend
from repro.core.network import init_float_params, quantize_params
from repro.kernels.lif_scan.lif_scan import lif_scan
from repro.kernels.quant_matmul.spike_matmul import spike_matmul
from repro.kernels.sparse_accum.sparse_accum import sparse_accum
from repro.launch.serve_snn import _build_net
from repro.serve.snn_engine import SNNServeEngine

T, B = 25, 1024  # main path: mnist-like T, the smoke's batch
CHUNK, LANES = 32, 8  # serving: the engine's chunk cap x max_batch


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the platform to TPU; drop every program traced meanwhile, so
    no later test in this process reuses one traced for the chip."""
    monkeypatch.setattr(lowering, "on_tpu", lambda: True)
    yield
    jax.clear_caches()


def _compiled_text(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("K,N", [(256, 128), (128, 10)], ids=["l0_256x128", "l1_128x10"])
def test_spike_matmul_compiles_for_v5e(one_chip, K, N):
    txt = _compiled_text(
        lambda s, w: spike_matmul(s, w),
        ((T * B, K), jnp.int8),
        ((K, N), jnp.int8),
        sharding=one_chip,
    )
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("N", [128, 10])
def test_lif_scan_compiles_for_v5e(one_chip, N):
    txt = _compiled_text(
        lambda c: lif_scan(c, theta_q=300, decay_k=200),
        ((T, B, N), jnp.int32),
        sharding=one_chip,
    )
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("n_in,N", [(256, 128), (128, 10)], ids=["l0_256x128", "l1_128x10"])
def test_sparse_accum_compiles_for_v5e(one_chip, n_in, N):
    budget = EventBackend().serve_budget(256, 0.10)
    E = CHUNK * LANES
    txt = _compiled_text(
        lambda v, i, w: sparse_accum(v, i, w),
        ((E, budget), jnp.int32),
        ((E, budget), jnp.int32),
        ((n_in, N), jnp.int32),
        sharding=one_chip,
    )
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("budget", [None, "serve"], ids=["lanes", "event_pallas"])
def test_lane_window_compiles_for_v5e(one_chip, on_tpu, budget):
    """The serving engine's whole jitted tick at the main path's shapes:
    the platform is steered to TPU while tracing, so the program is the
    one the chip runs (the sparse route holds the ``sparse_accum`` kernel)."""
    from repro.core.backend import batched_lane_init
    from repro.serve.snn_engine import _lane_window_packed

    net = _build_net(128, T)
    qparams, _ = quantize_params(net, init_float_params(jax.random.PRNGKey(0), net))
    if budget == "serve":
        budget = EventBackend().serve_budget(256, 0.10)

    def put(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    args = (
        jax.tree.map(put, qparams),
        jax.tree.map(put, batched_lane_init(net, LANES)),
        jax.ShapeDtypeStruct((CHUNK, LANES, 256), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((2, LANES), jnp.int32, sharding=one_chip),
    )
    def tick(q, st, x, m):
        return _lane_window_packed(net, q, st, x, m, "f32_exact", None, budget)

    txt = jax.jit(tick).lower(*args).compile().as_text()
    assert ("tpu_custom_call" in txt) == (budget is not None)


def test_f32_feed_forward_lowers_to_one_bf16_pass(one_chip):
    """The f32 lowering compiles for v5e at default precision: one bf16 MXU
    pass (no ``operand_precision=highest``) -- the reason ``f32_exact``
    applies the bf16 bound on TPU."""
    txt = _compiled_text(
        lambda x, w: lowering.f32_currents(x, w),
        ((CHUNK * LANES, 256), jnp.int32),
        ((256, 128), jnp.int32),
        sharding=one_chip,
    )
    dots = [l for l in txt.splitlines() if "convolution(" in l or " dot(" in l]
    assert dots, "no matmul in the compiled f32 feed-forward"
    assert not any("operand_precision={highest" in l for l in dots)


def test_population_sweep_feeds_the_mxu_int8_planes(one_chip):
    """The population program at ``shd-dse-sweep``'s size (32 candidates,
    batch 256, T=100, 700-200-20 Synaptic, ATA-T) on int8 rasters: every
    feed-forward product multiplies int8 operands under ``neura.core.ff``,
    the weights split into their planes once, before the step loop, and the
    planes' recombination (``256 (x @ hi) + x @ lo + 128 sum x``) stays in
    ff-scoped fusions, while the ATA-T product stays an int32 one under
    ``neura.core.recurrent``, in fusions that hold none of the recombination."""
    import re

    from repro.core.network import NetworkConfig
    from repro.core.snn_layer import IntLayerParams, LayerConfig, NeuronModel, ResetMode, Topology
    from repro.snn.train import _population_fwd

    syn = dict(neuron=NeuronModel.SYNAPTIC, reset=ResetMode.ZERO, w_bits=8, w_rec_bits=8)
    net = NetworkConfig(
        (LayerConfig(700, 200, topology=Topology.ATA_T, **syn), LayerConfig(200, 20, **syn)), 100
    )
    P, batch = 32, 256

    def put(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stacked = [
        IntLayerParams(put((P, 700, 200)), put((P, 200, 200)), put((P,))),
        IntLayerParams(put((P, 200, 20)), put((P, 0)), put((P,))),
    ]
    regs = put((P, 2))
    txt = _population_fwd.lower(net, stacked, regs, regs, put((100, batch, 700), jnp.int8))
    txt = txt.compile().as_text()
    assert " conditional(" not in txt

    comps, name = {}, None
    for line in txt.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name:
            comps[name].append(line)

    def inner(comp):
        """Every line of ``comp`` and of the computations it calls."""
        lines = list(comps.get(comp, []))
        for line in comps.get(comp, []):
            for callee in re.findall(r"calls=%([\w.\-]+)", line):
                lines += inner(callee)
        return lines

    entry = next(c for c in comps if c.startswith("main"))
    split = [l for l in comps[entry] if re.search(r"s8\[32,700,200\].* fusion\(", l)]
    assert split and all("neura.core.ff" in l for l in split)  # the planes, once per call

    types = dict(re.findall(r"%(\S+) = (\w+)\[", txt))
    conv = r"= \w+\[[^\]]*\]\S* convolution\(%(\S+), %(\S+)\).*?op_name=\"([^\"]*)\""
    convs = re.findall(conv, txt)
    int8 = [name for a, b, name in convs if types[a] == types[b] == "s8"]
    assert len(int8) == 4 and all("neura.core.ff" in n for n in int8)  # two planes, two layers
    rec = [(a, b) for a, b, name in convs if "neura.core.recurrent" in name]
    assert rec and all(types[a] == types[b] == "s32" for a, b in rec)

    fusions = [l for c in comps for l in comps[c] if " fusion(" in l]
    recurrent = [l for l in fusions if "/neura.core.recurrent/" in l]
    assert recurrent
    for line in recurrent:
        body = " ".join(inner(re.search(r"calls=%([\w.\-]+)", line).group(1)))
        assert "constant(128)" not in body and "constant(256)" not in body


# ---------------------------------------------------------------------------
# TPU rules, held on CPU by steering the platform
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("w_bits", [10, 12, 16])
def test_f32_rule_refuses_bf16_unsafe_weights_on_tpu(on_tpu, w_bits):
    assert not lowering.f32_exact(w_bits, 1, 256)
    assert lowering.f32_max_input(w_bits, 256) == 0
    assert lowering.f32_exact(6, 1, 256)  # 6-bit weights fit one bf16 pass
    assert not lowering.f32_exact(6, 257, 16)  # graded values past bf16's range


@pytest.mark.parametrize("w_bits", [10, 12, 16])
def test_f32_rule_admits_wide_weights_on_cpu(w_bits):
    assert lowering.f32_exact(w_bits, 1, 256)
    assert lowering.f32_max_input(w_bits, 256) >= 1


def test_f32_call_sites_ask_the_rule_on_tpu(on_tpu):
    """The engine's bounds and the event backend's dense lowering refuse
    f32 for 12-bit weights on TPU, and admit it for 6-bit weights."""
    for w_bits, ok in ((12, False), (6, True)):
        net = _build_net(128, T).replace_precisions(w_bits=w_bits)
        qparams, _ = quantize_params(net, init_float_params(jax.random.PRNGKey(0), net))
        eng = SNNServeEngine(net, qparams, max_batch=LANES, backend=EventBackend("pallas"))
        assert (eng._f32_input_max >= 1) == ok
        assert eng._deep_f32_ok == ok
        assert eng._sparse_val_max > 1  # the sparse kernel has no f32 bound
        want = lowering.F32 if ok else lowering.XLA_INT32
        assert EventBackend("pallas")._fixed_lowering(net.layers[0], None, 1) == want
        sparse = EventBackend("pallas")._fixed_lowering(net.layers[0], 64, 1)
        assert sparse == lowering.PALLAS_SPARSE


def test_no_interpret_mode_on_tpu(on_tpu):
    assert lowering.interpret() is False
    with pytest.raises(ValueError, match="interpret mode"):
        lowering.interpret(True)


@pytest.mark.parametrize(
    "w_bits,max_val,want",
    [
        (6, 1, lowering.PALLAS_INT8),
        (8, 127, lowering.PALLAS_INT8),
        (8, 128, lowering.XLA_INT32),
        (12, 1, lowering.XLA_INT32),
        (6, None, lowering.INT8_OR_INT32),
    ],
)
def test_mxu_feed_choice(w_bits, max_val, want):
    assert lowering.mxu_feed(w_bits, max_val) == want
