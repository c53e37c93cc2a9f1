"""Pallas TPU kernels — the native tier, analog of the reference's ``csrc/``.

Each module holds raw ``pallas_call`` kernels; the ``jax.custom_vjp`` wiring
and eligibility checks live one level up in ``apex_tpu/ops/*.py``.
"""


def exact_block(n: int, pref: int, quantum: int) -> int:
    """Largest ``quantum``-multiple divisor of ``n`` that is <= ``pref``, or
    0 when none exists. Blocks must tile the array exactly — Pallas pads
    partial edge blocks with *uninitialized* data, which would flow into
    softmax/sum accumulators. Shared by the attention and xentropy kernels.
    """
    b = min(pref, n)
    b -= b % quantum
    while b > quantum and n % b:
        b -= quantum
    return b if b >= quantum and n % b == 0 else 0
