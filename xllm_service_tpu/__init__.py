"""xllm_service_tpu — a TPU-native clustered LLM serving framework.

A ground-up rebuild of the capabilities of xllm-service (jd-opensource's
cluster service layer, see /root/reference) plus the engine tier it delegates
to, designed TPU-first:

- Engine tier: JAX/XLA/Pallas continuous-batching inference runtime with a
  paged KV cache, pjit/shard_map parallelism over `jax.sharding.Mesh`, and
  Pallas kernels for the hot ops (paged attention).
- Service tier: OpenAI-compatible HTTP front end, etcd-style coordination
  (with an in-memory backend for tests), instance registry with dynamic
  prefill/decode role flipping, global prefix-cache index keyed by chained
  murmur3 block hashes, and round-robin / cache-aware / SLO-aware routing.

Layering follows SURVEY.md; reference file:line citations appear in each
module's docstring.
"""

import time

__version__ = "0.1.0"
# When this process first imported the package: where
# `xllm_engine_first_step_seconds` counts from (obs/startup.py).
IMPORTED_AT = time.monotonic()
