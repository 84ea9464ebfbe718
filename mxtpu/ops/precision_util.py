"""MXU precision + accumulation policy for contraction ops.

The package-global ``jax_default_matmul_precision='float32'``
(mxtpu/__init__.py) exists to keep FLOAT32 contractions honest: without
it, XLA:TPU silently truncates f32 operands to one-pass bf16. That global
also tags BF16 contractions HIGHEST; ``mxu_precision(*operands)`` overrides
to DEFAULT when every floating operand is sub-f32 (bf16/f16) and returns
None (inherit the honest global) otherwise — the correct policy, though
measurement showed bf16-at-HIGHEST was NOT the historical throughput
ceiling (83 vs 85 TFLOP/s; an earlier 3-6x claim was a sync artifact —
see PERF.md "RETRACTED").

What DOES move the MXU (PERF.md "achievable ceiling"): asking low-precision
contractions for an **f32 accumulator output** (``preferred_element_type``)
— 102 -> 140 TFLOP/s on an 8k matmul — implemented by ``acc_dtype``/
``dot_acc`` here. Convolutions do not ask for it: end to end it lost
(PERF.md §6, PR 28).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_LOW = (jnp.bfloat16, jnp.float16)


def mxu_precision(*operands):
    """Precision override for lax dot/conv given the actual operands."""
    dtypes = [o.dtype for o in operands if hasattr(o, "dtype")]
    if dtypes and all(d in _LOW for d in dtypes):
        return lax.Precision.DEFAULT
    return None


def acc_dtype(*operands):
    """preferred_element_type for a contraction over these operands.

    For all-bf16/f16 operands, requesting an f32 accumulator output makes
    XLA:TPU pick a measurably faster MXU schedule than the bf16-out form —
    tools/perf_peak.py measures 102 -> 140 TFLOP/s on an 8k x 8k matmul
    (the cast back to bf16 fuses into the epilogue and keeps the gain).
    Numerics only improve: the accumulator was f32 either way; this keeps
    it f32 through the epilogue instead of rounding per-tile.

    Returns jnp.float32 for low-precision operands, else None. jax 0.9
    supports preferred_element_type under autodiff for dot_general but NOT
    for conv_general_dilated (its transpose rule rejects the mixed-dtype
    cotangent), so this is a matmul policy only.
    """
    dtypes = [o.dtype for o in operands if hasattr(o, "dtype")]
    if dtypes and all(d in _LOW for d in dtypes):
        return jnp.float32
    return None


def acc_out_dtype(*operands):
    """Output dtype after the f32-accumulate round trip: the operands'
    PROMOTED dtype (bf16 x bf16 -> bf16, but bf16 x f16 -> f32 exactly as
    jnp promotion produced before the fast path existed — casting to the
    first operand's dtype would silently change the public op's dtype and
    make it argument-order dependent)."""
    return jnp.result_type(*operands)


def contract_acc(contraction, a, b, **kwargs):
    """ONE copy of the fast-accumulate policy for any jnp/lax contraction
    callable taking (a, b, ..., precision=, preferred_element_type=): f32
    accumulator for low-precision operands with the result cast back to the
    operands' promoted dtype; full-precision operands inherit the honest-f32
    global. Used by FullyConnected, dot, batch_dot and the RNN gate matmuls
    so the policy cannot drift between call sites."""
    pet = acc_dtype(a, b)
    out = contraction(a, b, precision=mxu_precision(a, b),
                      preferred_element_type=pet, **kwargs)
    return out.astype(acc_out_dtype(a, b)) if pet is not None else out


def dot_acc(x, w, dimension_numbers):
    """lax.dot_general under the fast-accumulate policy (contract_acc)."""
    return contract_acc(lax.dot_general, x, w,
                        dimension_numbers=dimension_numbers)
