"""Device milliseconds per execution of the decode program in operations
whose innermost named scope is ``mlp`` (``models/layers.py`` ``mlp``)."""
from bench import program_trace as pt


def read(r):
    ms = pt.decode_ms(r)
    return None if ms is None else ms.get("mlp", 0.0)
