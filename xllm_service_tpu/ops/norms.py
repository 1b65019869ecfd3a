"""RMSNorm (engine-tier op; SURVEY.md §2.3). Computed in float32 for
stability, cast back to input dtype; XLA fuses this into adjacent ops."""

from __future__ import annotations

import jax.numpy as jnp

from xllm_service_tpu.obs.spans import region


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf / jnp.sqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


@region("norm")
def block_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    """A block's pre-mixer or pre-MLP RMSNorm: `rms_norm` under the `norm`
    device region (a norm inside a projection or the head stays in that
    region: obs.spans.DEVICE_REGIONS)."""
    return rms_norm(x, weight, eps)
