"""The two operations every family's layer is made of: the matmul that
takes int8 weight-only leaves (beside :mod:`runbookai_tpu.ops.qmm_pallas`,
which it dispatches to) and RMSNorm in float32.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp


def qmm(x: jnp.ndarray, w: Any, impl: str = "xla") -> jnp.ndarray:
    """Matmul that accepts int8 weight-only quantized weights.

    Quantized leaves are ``{"q": int8 [.., in, out], "s": f32 [.., 1, out]}``
    (:mod:`runbookai_tpu.models.quant`). The matmul runs on the MXU in the
    activation dtype (int8→bf16 cast is exact) and the per-output-channel
    scale applies to the result — identical math to dequantize-first, since
    the scale is constant along the contraction.

    ``impl="pallas"`` reads the int8 matrix through the Pallas kernel
    (:mod:`runbookai_tpu.ops.qmm_pallas`) at decode/verify shapes — the
    convert happens in VMEM, so HBM moves half the bf16 bytes by
    construction instead of by fusion luck. A leaf that also carries
    ``"layer"`` holds the layer scan's STACKED ``q [L, in, out]`` with the
    layer's number and that layer's scales (``models/llama.py``
    ``_forward_hidden``, which has checked the shape): the kernel reads that
    layer's matrix where it lies. Shapes the kernel does not cover (chunked
    prefill M, ragged dims, unquantized leaves) fall back to the XLA
    expression below, same math.
    """
    if isinstance(w, dict):
        if "layer" in w or (impl == "pallas" and w["q"].ndim == 2):
            # (the Pallas machinery loads where a kernel is asked for)
            from runbookai_tpu.ops.qmm_pallas import (
                qmm_pallas,
                qmm_pallas_eligible,
            )

            lead = x.shape[:-1]
            k_dim, n = w["q"].shape[-2:]
            if "layer" in w or qmm_pallas_eligible(math.prod(lead), k_dim, n):
                out = qmm_pallas(
                    x.reshape(-1, k_dim), w["q"], w["s"].reshape(1, n),
                    w.get("layer"),
                    interpret=jax.default_backend() == "cpu",
                )
                return out.reshape(*lead, n)
        return (x @ w["q"].astype(x.dtype)) * w["s"].astype(x.dtype)
    return x @ w


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (norm * weight).astype(x.dtype)
