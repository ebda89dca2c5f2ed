"""Pallas TPU kernels for the serving hot spots (DESIGN.md §6).

Each kernel: pl.pallas_call + explicit BlockSpec VMEM tiling; ops.py holds
the dispatch rule (the kernel on a TPU, the jnp path elsewhere) and ref.py
the pure-jnp oracles the tests sweep against."""

from repro.kernels.mamba_scan import mamba_chunked_scan
from repro.kernels.moe_gemm import fused_moe_ffn
from repro.kernels.paged_attention import paged_flash_attention
from repro.kernels.rwkv6_scan import rwkv6_chunked_scan

__all__ = ["fused_moe_ffn", "mamba_chunked_scan",
           "paged_flash_attention", "rwkv6_chunked_scan"]
