"""Public entry points of the Pallas kernels.

Each kernel decides at trace time whether it is interpreted (CPU) or
compiled through Mosaic (TPU); see ``repro.kernels.platform``.
"""
from repro.kernels.block_score import block_score  # noqa: F401
from repro.kernels.flash_prefill import flash_prefill  # noqa: F401
from repro.kernels.gather_blocks import (  # noqa: F401
    gather_blocks, gather_blocks_hkv)
from repro.kernels.quant_blocks import (  # noqa: F401
    dequantize_blocks, dequantize_scatter_blocks, quantize_blocks)
from repro.kernels.scatter_blocks import (  # noqa: F401
    scatter_blocks, scatter_blocks_hkv)
from repro.kernels.sparse_decode_attention import (  # noqa: F401
    sparse_decode_attention)
