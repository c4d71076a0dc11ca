"""Batched serving with Flex-plorer-chosen weight precision.

    PYTHONPATH=src python examples/serve_quantized.py

Serves a reduced gemma2-family model with continuous batching, twice: at
full precision and with the paper's technique applied (int8 attention +
int4 MLP weights via the quant_matmul path).  Prints the outputs side by
side and the modeled decode-step memory traffic for the full-size config --
the number the decode_32k roofline cells are bound by.
"""

import time

import jax
import numpy as np

from repro.core.precision import PrecisionPolicy
from repro.distributed.structural import structural_bytes
from repro.models.registry import SHAPES, get_arch
from repro.serve.engine import Request, ServeEngine


def main():
    arch = get_arch("gemma2-27b")
    params = arch.init_params(jax.random.PRNGKey(0), arch.reduced_config)
    prompts = [np.asarray([11, 42, 7]), np.asarray([99, 3]), np.asarray([5, 5, 5, 5])]

    results = {}
    for label, policy in [
        ("bf16", None),
        ("int8-attn/int4-mlp", PrecisionPolicy(rules=(
            (r"(wq|wk|wv|wo)$", 8), (r"(w_gate|w_up|w_down)$", 4),
        ))),
    ]:
        eng = ServeEngine(arch, params, max_batch=2, max_len=64, quant=policy)
        t0 = time.time()
        done = eng.run([Request(uid=i, prompt=p, max_new_tokens=8) for i, p in enumerate(prompts)])
        results[label] = {r.uid: r.generated for r in done}
        print(f"[{label:>18}] served {len(done)} requests in {time.time()-t0:.1f}s")
        for uid in sorted(results[label]):
            print(f"    req{uid}: {results[label][uid]}")

    agree = sum(
        results["bf16"][u] == results["int8-attn/int4-mlp"][u] for u in results["bf16"]
    )
    print(
        f"\ngreedy outputs identical under int8/int4: {agree}/{len(prompts)} "
        "(random-init weights give near-uniform logits, so argmax is "
        "quantization-sensitive here; the int8-KV path is "
        "greedy-preserving in tests/test_precision_paths.py)"
    )

    shape = SHAPES["decode_32k"]
    base = structural_bytes(arch, shape)["total"]
    q8 = structural_bytes(arch, shape, quant_bits=8)["total"]
    q4 = structural_bytes(arch, shape, quant_bits=4)["total"]
    print(
        f"\nfull-size gemma2-27b decode_32k memory traffic per device per step:\n"
        f"  bf16/f32 weights: {base/1e9:.2f} GB  -> {base/819e9*1e6:.0f} us/step at HBM roofline\n"
        f"  int8 weights:     {q8/1e9:.2f} GB  -> {q8/819e9*1e6:.0f} us/step\n"
        f"  int4 weights:     {q4/1e9:.2f} GB  -> {q4/819e9*1e6:.0f} us/step"
    )


if __name__ == "__main__":
    main()
