"""Rotary position embeddings.

Engine-tier op (the reference's RoPE lives in the absent CUDA engine —
SURVEY.md §2.3). Pure jnp: XLA fuses the sin/cos + elementwise rotation into
surrounding matmuls on TPU, so no Pallas kernel is warranted here.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies [head_dim/2], float32."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponent)


def rope_parameters(head_dim: int, cfg) -> tuple:
    """(inv_freq [head_dim/2] np.float32, output_scale float) honoring HF
    `rope_scaling` semantics (transformers modeling_rope_utils):

      - ""         plain theta frequencies
      - "linear"   positions stretched by `factor` (inv_freq / factor)
      - "dynamic"  NTK-scaled base, FROZEN at the extended range
                   original * factor. HF recomputes the base per forward
                   from the live sequence length, which is incoherent with
                   a paged KV cache (earlier keys would need re-rotation);
                   freezing at the full extended range is the serving
                   semantic (matches HF exactly for a single forward of
                   that length).
      - "llama3"   per-band wavelength interpolation (Llama-3.1/3.2)
      - "longrope" per-band short/long factor tables (Phi-3 128k).
                   rope_parameters returns the SHORT-table frequencies
                   (exact HF for any sequence within the original
                   context); apply_rope_scaled selects short/long PER
                   POSITION (pos < original -> short), which is coherent
                   with a paged KV cache — HF instead switches the whole
                   table per forward once seq_len exceeds the original,
                   retroactively re-rotating earlier positions, which a
                   cache-carrying engine cannot do (vLLM makes the same
                   per-position choice). Output additionally scales by
                   sqrt(1 + ln(factor)/ln(orig)) per HF, in BOTH modes
                   (HF fixes attention_scaling at init from the config
                   factor).

    `cfg` is duck-typed (ModelConfig or any object with the rope_* fields)
    so this op layer needs no import from models/. All math is numpy —
    static at trace time, so under jit the table is a compile-time
    constant. Unrecognized types raise at config parse (runtime/weights.
    config_from_hf), never here.
    """
    theta = float(cfg.rope_theta)
    exponent = np.arange(0, head_dim, 2, dtype=np.float32) / head_dim
    inv = _plain_inv_freq(head_dim, theta)
    typ = getattr(cfg, "rope_scaling_type", "") or ""
    if not typ:
        return inv, 1.0
    factor = float(getattr(cfg, "rope_scaling_factor", 1.0))
    orig = _orig_max_position(cfg)
    if typ == "linear":
        return inv / factor, 1.0
    if typ == "dynamic":
        # HF: base * ((factor * seq_len / orig) - (factor - 1)) ** (d/(d-2)),
        # here with seq_len pinned to orig * factor.
        base = theta * (factor * factor - factor + 1.0) ** (
            head_dim / (head_dim - 2)
        )
        return (1.0 / base**exponent).astype(np.float32), 1.0
    if typ == "llama3":
        lo = float(getattr(cfg, "rope_low_freq_factor", 1.0))
        hi = float(getattr(cfg, "rope_high_freq_factor", 4.0))
        low_wl, high_wl = orig / lo, orig / hi
        wavelen = 2.0 * np.pi / inv
        scaled = np.where(wavelen > low_wl, inv / factor, inv)
        smooth = (orig / wavelen - lo) / (hi - lo)
        smoothed = (1.0 - smooth) * scaled / factor + smooth * scaled
        medium = (wavelen >= high_wl) & (wavelen <= low_wl)
        return np.where(medium, smoothed, scaled).astype(np.float32), 1.0
    if typ == "longrope":
        short, _, mscale = _longrope_tables(head_dim, cfg, inv, orig)
        return short, mscale
    if typ == "yarn":
        # HF _compute_yarn_parameters (arxiv 2309.00071): blend the
        # interpolated (inv/factor) and extrapolated (inv) tables with a
        # linear ramp between the beta_fast/beta_slow correction dims;
        # the attention factor follows the paper's 0.1*ln(s)+1 mscale —
        # DeepSeek configs supply mscale/mscale_all_dim and get the
        # RATIO (their checkpoints also scale the softmax temperature,
        # which the MLA attention applies — models/deepseek.py).
        bf = float(getattr(cfg, "rope_beta_fast", 32.0)) or 32.0
        bs = float(getattr(cfg, "rope_beta_slow", 1.0)) or 1.0
        msc = float(getattr(cfg, "rope_mscale", 0.0))
        msc_all = float(getattr(cfg, "rope_mscale_all_dim", 0.0))
        att = float(getattr(cfg, "rope_attention_factor", 0.0))
        if not att:
            if msc and msc_all:
                att = yarn_mscale(factor, msc) / yarn_mscale(
                    factor, msc_all
                )
            else:
                att = yarn_mscale(factor)

        def corr_dim(rot: float) -> float:
            return (
                head_dim * math.log(orig / (rot * 2.0 * math.pi))
            ) / (2.0 * math.log(theta))

        low, high = corr_dim(bf), corr_dim(bs)
        if getattr(cfg, "rope_scaling_truncate", True):
            low, high = math.floor(low), math.ceil(high)
        low, high = max(low, 0), min(high, head_dim - 1)
        if low == high:
            high += 0.001  # HF's singularity guard
        ramp = np.clip(
            (np.arange(head_dim // 2, dtype=np.float32) - low)
            / (high - low),
            0.0, 1.0,
        )
        extrap = 1.0 - ramp
        return (
            (inv / factor) * (1.0 - extrap) + inv * extrap
        ).astype(np.float32), float(att)
    raise NotImplementedError(f"rope_scaling type {typ!r}")


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """The yarn paper's attention-temperature term (HF get_mscale);
    DeepSeek's attention ALSO multiplies its softmax scale by
    yarn_mscale(factor, mscale_all_dim)^2 — models/deepseek.py."""
    if scale <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def _plain_inv_freq(head_dim: int, theta: float) -> np.ndarray:
    """Unscaled inverse-frequency table — the single base-convention
    source for every scaling type (numpy: static at trace time)."""
    exponent = np.arange(0, head_dim, 2, dtype=np.float32) / head_dim
    return (1.0 / theta**exponent).astype(np.float32)


def _orig_max_position(cfg) -> int:
    return int(getattr(cfg, "rope_original_max_position", 0)) or int(
        cfg.max_position_embeddings
    )


def _longrope_tables(head_dim: int, cfg, inv: np.ndarray, orig: int):
    """(short_inv_freq, long_inv_freq, attention_scale) for longrope."""
    tables = []
    for name in ("rope_short_factor", "rope_long_factor"):
        ext = np.asarray(getattr(cfg, name), dtype=np.float32)
        if ext.shape != inv.shape:
            raise ValueError(
                f"longrope {name} table has {ext.shape[0]} entries; "
                f"head_dim {head_dim} needs {inv.shape[0]}"
            )
        tables.append((inv / ext).astype(np.float32))
    mscale = float(getattr(cfg, "rope_attention_factor", 0.0))
    if not mscale:
        ctx_factor = cfg.max_position_embeddings / orig
        mscale = (
            math.sqrt(1.0 + math.log(ctx_factor) / math.log(orig))
            if ctx_factor > 1.0
            else 1.0
        )
    return tables[0], tables[1], mscale


def _rotate(
    x: jnp.ndarray, angles: jnp.ndarray, scale: float = 1.0
) -> jnp.ndarray:
    """Split-half rotation by per-(token, frequency) `angles` [..., half]
    — the single rotation convention both rope variants share (a future
    convention change must hit both or equal-streams M-RoPE would
    silently diverge from the standard path decode relies on). `scale`
    multiplies cos AND sin (HF longrope attention_factor placement), i.e.
    scales the rotated output."""
    half = x.shape[-1] // 2
    cos = scale * jnp.cos(angles)[..., None, :]  # [..., 1, half]
    sin = scale * jnp.sin(angles)[..., None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def apply_mrope(
    x: jnp.ndarray,  # [T, num_heads, head_dim]
    positions3: jnp.ndarray,  # [3, T] int32 — (t, h, w) position streams
    theta: float,
    section,  # static tuple of half-dim section sizes, e.g. (16, 24, 24)
) -> jnp.ndarray:
    """Multimodal rotary embedding (Qwen2-VL M-RoPE, HF
    apply_multimodal_rotary_pos_emb): frequency band i takes its ANGLE
    from position stream section_of(i) — the first `section[0]` inverse
    frequencies from the temporal stream, the next `section[1]` from the
    height stream, the rest from width. When the three streams are equal
    (every text token, every decode step) this IS apply_rope; image
    spans inside a prompt are where the streams diverge."""
    import numpy as np

    half = x.shape[-1] // 2
    assert sum(section) == half, (section, half)
    inv_freq = rope_frequencies(x.shape[-1], theta)  # [half]
    sel = np.repeat(np.arange(len(section)), section)  # [half] -> stream id
    pos_sel = positions3[jnp.asarray(sel)]  # [half, T]
    angles = pos_sel.T.astype(jnp.float32) * inv_freq  # [T, half]
    return _rotate(x, angles)


def apply_rope(
    x: jnp.ndarray,  # [..., num_heads, head_dim]
    positions: jnp.ndarray,  # [...] int32, broadcastable to x's batch dims
    theta: float,
) -> jnp.ndarray:
    """Rotate pairs (x[2i], x[2i+1]) by positions * inv_freq[i].

    Uses the interleaved-pair convention expressed as split-half rotation on
    a de-interleaved view — matches HF Llama when weights are loaded with the
    standard permutation; for random-init + self-consistent decode any
    consistent convention is exact.
    """
    inv_freq = rope_frequencies(x.shape[-1], theta)  # [half]
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., half]
    return _rotate(x, angles)


def apply_rope_scaled(
    x: jnp.ndarray,  # [..., num_heads, head_dim]
    positions: jnp.ndarray,  # [...] int32, broadcastable to x's batch dims
    cfg,  # ModelConfig-like: rope_theta + rope_scaling_* fields
) -> jnp.ndarray:
    """apply_rope honoring the config's HF rope_scaling (rope_parameters).

    The model call sites route through here; configs without scaling
    (rope_scaling_type == "") reduce exactly to apply_rope. longrope
    selects the short/long table PER POSITION (pos < original context ->
    short) — exact HF inside the original context, cache-coherent beyond
    it (see rope_parameters docstring)."""
    head_dim = x.shape[-1]
    pos = positions[..., None].astype(jnp.float32)
    if getattr(cfg, "rope_scaling_type", "") == "longrope":
        inv = _plain_inv_freq(head_dim, float(cfg.rope_theta))
        orig = _orig_max_position(cfg)
        short_t, long_t, scale = _longrope_tables(head_dim, cfg, inv, orig)
        angles = jnp.where(pos < orig, pos * short_t, pos * long_t)
        return _rotate(x, angles, scale)
    inv_freq, scale = rope_parameters(head_dim, cfg)
    angles = pos * inv_freq
    return _rotate(x, angles, scale)


def apply_partial_rope(
    x: jnp.ndarray,  # [..., num_heads, head_dim]
    positions: jnp.ndarray,  # [...] int32, broadcastable to x's batch dims
    theta: float,
    rotary_dim: int,
) -> jnp.ndarray:
    """apply_rope on lanes [0, rotary_dim) of every head (frequencies over
    `rotary_dim`, split-half pairs inside it); the other lanes pass."""
    if rotary_dim >= x.shape[-1]:
        return apply_rope(x, positions, theta)
    rot = apply_rope(x[..., :rotary_dim], positions, theta)
    return jnp.concatenate([rot, x[..., rotary_dim:]], axis=-1)


def apply_partial_rope_table(
    x: jnp.ndarray,  # [..., num_heads, head_dim]
    positions: jnp.ndarray,  # [...] int32, broadcastable to x's batch dims
    inv_freq,  # [rotary_dim / 2]: a scaled table (rope_parameters)
    scale: float,
    rotary_dim: int,
) -> jnp.ndarray:
    """apply_partial_rope by a table made at build: lanes [0, rotary_dim)
    of every head rotate by `positions * inv_freq` with `scale` on cos and
    sin (YaRN's attention factor: the rotated lanes of q AND k carry it,
    the lanes that pass do not)."""
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq, jnp.float32)
    rot = _rotate(x[..., :rotary_dim], angles, scale)
    if rotary_dim >= x.shape[-1]:
        return rot
    return jnp.concatenate([rot, x[..., rotary_dim:]], axis=-1)
