"""Pallas TPU kernels for the substrate hot-spots (validated on CPU with
interpret=True against the ref.py oracles).

The paper itself contributes no kernel — its contribution is the outer
communication schedule — so these serve the schedule and the model
substrate:
  * gt_update            — fused FedGDA-GT inner update (one HBM pass)
  * compress_correction  — fused select+quantize+error-feedback on tracking
                           corrections (CompressedGT / QuantizedGT)
  * pack_payload         — fused select+quantize+BIT-PACK to the actual
                           sparse wire format (and the fused unpack+
                           dequant+scatter-add inverse) for fed.transport
  * flash_attention — blocked online-softmax attention (causal/window/softcap)
  * selective_scan  — fused Mamba-1 selective scan with its own backward,
                      the state carried in VMEM (models/mamba.py on a TPU)
"""
from .gt_update import gt_update_2d
from .compress_correction import (
    compress_correction_2d,
    compress_leaf,
    fusable_leaf,
)
from .pack_payload import pack_payload_2d, unpack_payload_2d
from .flash_attention import flash_attention
from .ssm_scan import selective_scan
from .ops import (
    grouped_flash_attention,
    make_gt_update_fn,
)
from . import ref

__all__ = [
    "gt_update_2d",
    "compress_correction_2d",
    "compress_leaf",
    "fusable_leaf",
    "pack_payload_2d",
    "unpack_payload_2d",
    "flash_attention",
    "selective_scan",
    "grouped_flash_attention",
    "make_gt_update_fn",
    "ref",
]
