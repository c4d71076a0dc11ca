"""Mean self time of the ``neura.serve.tick`` span: the tick less its launch and its readback.

What is left is the tick's packing of inputs and its completion bookkeeping.
"""

from perfharness import spans


def read(run):
    children = ("neura.serve.launch", "neura.serve.readback")
    return spans.self_ms(run.trace, "neura.serve.tick", children)
