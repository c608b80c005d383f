"""Pallas TPU kernels (validated in interpret mode on CPU).

  fused_dora       — base matmul + DoRA-decomposed LoRA adapter, one pass
  flash_attention  — causal/sliding-window flash attention, GQA; the
                     models' training/prefill core on TPU
  ssd_scan         — Mamba-2 SSD chunked scan with VMEM-resident state
  batched_lora     — BGMV: per-row adapter gather for mixed-tenant serving
  quant_matmul     — dequant-fused int8/int4 backbone matmul for serving
"""
from repro.kernels.fused_dora.ops import fused_dora, fused_dora_ref  # noqa: F401
from repro.kernels.flash_attention.ops import (attention_ref,  # noqa: F401
                                              flash_attention,
                                              flash_attention_causal)
from repro.kernels.ssd_scan.ops import ssd_scan, ssd_ref, ssd_naive  # noqa: F401
from repro.kernels.batched_lora.ops import (bgmv, bgmv_mag,  # noqa: F401
                                            bgmv_mag_ref, bgmv_ref)
from repro.kernels.quant_matmul.ops import (dequantize,  # noqa: F401
                                            quant_matmul, quant_matmul_ref,
                                            quantize_backbone, quantize_int4,
                                            quantize_int8, unpack_int4)
