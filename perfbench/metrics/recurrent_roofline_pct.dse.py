"""The ATA-T recurrent product's share of the chip's int8 peak, in percent.

Its dense operations per population call (``recurrent_ops``) over its device
time per call (``recurrent_ms.dse``) over the published int8 peak of one chip.
The product is dense whatever the spikes: ``prev_spikes [batch, n] @ W_rec [n, n]``
at every step, for every candidate a chip holds. The factors are the program's
own counters: ``candidates``, ``shards`` and ``recurrent_macs`` (the sum of
``n_out ** 2`` over ATA-T layers) of ``neura.dse.stack``, and ``samples`` and
``steps`` of ``neura.dse.batch``.
"""

from perfharness import opmeta, spans

PROGRAMS = ("_population_fwd", "_population_sharded_jit")


def recurrent_ops(candidates: float, steps: float, batch: float, recurrent_macs: float) -> float:
    """Operations of one call: 2 x candidates x T x batch x sum of n_out ** 2."""
    return 2.0 * candidates * steps * batch * recurrent_macs


def read(run):
    ms = opmeta.scoped_ms(run.trace, "neura.core.recurrent", PROGRAMS)
    if not ms:
        return None
    args = [
        spans.arg_mean(run.trace, "neura.dse.stack", "candidates"),
        spans.arg_mean(run.trace, "neura.dse.stack", "shards"),
        spans.arg_mean(run.trace, "neura.dse.stack", "recurrent_macs"),
        spans.arg_mean(run.trace, "neura.dse.batch", "steps"),
        spans.arg_mean(run.trace, "neura.dse.batch", "samples"),
    ]
    if any(a is None for a in args):
        return None
    candidates, shards, macs, steps, batch = args
    per_chip = -(-candidates // shards)  # a mesh pads the population to whole shards
    ops = recurrent_ops(per_chip, steps, batch, macs)
    return 100.0 * ops / (ms * 1e-3) / run.peaks["int8_ops_per_s"]
