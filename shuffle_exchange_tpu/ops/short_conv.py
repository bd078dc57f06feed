"""The gated short convolution (LFM2's ``conv`` layers; mixer "sconv" of
``models/transformer.py``): what lies between the mixer's two projections.

The layer is ``[B, C, x] = split3(y W_in)``, ``u = B * x``, a causal depthwise
convolution of ``K`` taps over u (no bias, no activation), ``C *`` its result,
then ``W_out``: no heads, no softmax, no state but the last ``K - 1`` rows of
u. :func:`sconv_mix` is the pass between the projections, written once: the
gate before, the taps (``gated_delta.causal_conv1d``: the oracle for them), the
gate after, all in float32 and rounded ONCE, at the write. It is the body on
every backend today (:func:`sconv_route` says "xla") and the oracle of any
kernel that takes its place: by its bytes the pass needs one read of
[rows, 3C] and one write of [rows, C] forward, and a read of [rows, 3C] and
d[rows, C] and a write of d[rows, 3C] backward.
"""

from __future__ import annotations


def sconv_route(bcx, w) -> str:
    """Which form :func:`sconv_mix` runs for these operands, read off the
    backend and the shapes as ``gated_delta.prologue_route`` does: "xla" (the
    one form there is; a kernel would answer "pallas" here at its shapes)."""
    del bcx, w
    return "xla"


def sconv_mix(bcx, w):
    """``bcx`` [B, T, 3C] as the input projection wrote it (three blocks of
    C channels: the gate before B, the gate after C, the signal x, in this
    order) and the taps ``w`` [K, C] -> [B, T, C] in ``bcx``'s dtype:
    ``C[t] * sum_j w[j] * (B * x)[t - (K - 1) + j]``, (B * x) zero before
    position 0 of each sequence. Products and the taps' sum are float32; the
    result is rounded once."""
    import jax.numpy as jnp

    from .gated_delta import causal_conv1d

    C = w.shape[-1]
    if bcx.shape[-1] != 3 * C:
        raise ValueError(f"sconv_mix: {bcx.shape[-1]} channels for taps over {C}: "
                         "the projection writes three blocks of the taps' width")
    f32 = jnp.float32
    gate_in, gate_out, x = (bcx[..., i * C:(i + 1) * C].astype(f32) for i in range(3))
    # float32 in, float32 out: ``causal_conv1d`` returns its input's dtype
    return (gate_out * causal_conv1d(gate_in * x, w)).astype(bcx.dtype)
