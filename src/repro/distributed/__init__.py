from .compression import (
    compressed_psum,
    dequantize_int8,
    ef_compress,
    quantize_int8,
    tree_compressed_psum,
    tree_ef_state,
)
from .pipeline import pipeline_apply

__all__ = [k for k in dir() if not k.startswith("_")]
