"""Hand-written Pallas TPU kernels.

The reference ships hand kernels where its compilers fell short (CUDA
.cu files, cuDNN call-outs); here XLA covers almost everything and this
package holds the few deliberate exceptions, written with Pallas
(MXU/VMEM-aware blocking). Kernels run compiled on TPU and in Pallas
interpret mode elsewhere, so their tests execute on any backend.
"""

from .flash_attention import (flash_attention, flash_decode,
                              dense_decode_with_lse)
from .paged_decode import paged_attention
from .latent_decode import (latent_decode, latent_decode_reference,
                            latent_row_store)
from .grouped_matmul import grouped_matmul, grouped_matmul_reference
from .kv_decode import kv_decode, kv_decode_reference
from .chunk_attention import chunk_attention

__all__ = ["flash_attention", "flash_decode",
           "dense_decode_with_lse", "paged_attention",
           "latent_decode", "latent_decode_reference", "latent_row_store",
           "grouped_matmul", "grouped_matmul_reference",
           "kv_decode", "kv_decode_reference", "chunk_attention"]
