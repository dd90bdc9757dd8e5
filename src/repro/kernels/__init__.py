"""Pallas TPU kernels for the substrate's compute hot-spots.

The paper (a runtime/scheduling contribution) has no kernel of its own
(DESIGN.md §2); these cover the model substrate:

    flash_attention — causal/SWA/GQA online-softmax attention,
                      BlockSpec VMEM tiling, f32 scratch accumulators
    ssd_scan        — Mamba-2 SSD chunked scan with VMEM-resident state
    rmsnorm         — fused single-pass RMSNorm
    paged_attention — single-token decode over a paged KV arena
    kv_append       — decode's new K/V rows written into a dense cache
                      leaf in its stored, position-minor layout

ops.py exposes jit'd wrappers with interpret-mode CPU fallback;
ref.py holds the pure-jnp oracles used by tests/test_kernels.py.
"""

from repro.kernels.ops import flash_attention, rmsnorm, ssd_scan

__all__ = ["flash_attention", "rmsnorm", "ssd_scan"]
