"""Device milliseconds per execution of the decode program in operations
whose innermost named scope is ``attn`` or its cache update ``kv_write``
(``models/layers.py`` ``decode_attention``)."""
from bench import program_trace as pt


def read(r):
    ms = pt.decode_ms(r)
    return None if ms is None else ms.get("attn", 0.0) + ms.get("kv_write", 0.0)
