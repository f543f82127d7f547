"""Operations and bytes the router's work needs, from its shapes.

Counted from the algorithm, never from the program: the SGLD potential
kernels and the dueling selection (the encoder's count is its module's
``flops_per_query``, beside the block it counts). Used for roofline shares
and ``serve_mfu``.
"""
from __future__ import annotations


def sgld_kernel_cost(kind: str, m: int, k: int, d: int, chains: int):
    """(flops, bytes) of one SGLD potential kernel call over ``chains``
    vmapped chains, at the real arm count ``k``.

    The whole step (forward + backward) is ``10 m K d`` FLOPs a chain: the
    forward tile computes the score numerator and denominator (two
    ``m x d x K`` matmuls, 4 m K d), the backward recomputes both and
    contracts the weights back onto the arms (6 m K d). Bytes, in f32: each
    call reads a chain's minibatch rows (m d), the arm table twice (2 K d)
    and theta (d), the copied model's ``4 c (2 m d + 4 K d + 2 d)`` a step
    split evenly between the two calls."""
    if kind == "fwd":
        flops = 4.0 * m * k * d * chains
        bytes_ = 4.0 * chains * (m * d + 2.0 * k * d + d)
    elif kind == "bwd":
        flops = 6.0 * m * k * d * chains
        bytes_ = 4.0 * chains * (m * d + 2.0 * k * d + d)
    else:
        raise ValueError(f"unknown SGLD kernel kind {kind!r}")
    return flops, bytes_


def select_flops(n: int, k: int, d: int) -> float:
    """Dueling selection of ``n`` rows: the shared denominator matmul and
    one numerator matmul per theta, 2 FLOPs a MAC."""
    return 2.0 * 3.0 * n * k * d


def roofline_seconds(flops: float, bytes_: float, peaks) -> tuple:
    """(least seconds, bound) on the chip: the larger of compute and
    memory time, and which of the two it is."""
    t_c = flops / peaks.flops_bf16
    t_m = bytes_ / peaks.hbm_bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
