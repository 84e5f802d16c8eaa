"""Pallas TPU kernels for hot ops."""

from .flash_attention import (chunk_attention, decode_attention,  # noqa: F401
                              flash_attention, flash_decode_attention,
                              flash_latent_chunk_attention,
                              flash_latent_decode_attention,
                              flash_paged_chunk_attention,
                              flash_paged_decode_attention,
                              flash_paged_decode_quant_attention,
                              gather_pages, kv_dequantize, kv_quantize,
                              latent_chunk_attention,
                              latent_decode_attention,
                              paged_chunk_attention, paged_decode_attention,
                              window_attention)
