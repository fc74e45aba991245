"""Operations and bytes that ALS needs, from the problem's sizes alone.

Counts what the algorithm needs, not what an implementation does, so the
roofline share reads the same work whatever implements it. Per iteration,
both half-steps:

- normal equations: per edge and side, one rank-1 update of a KxK matrix
  (2 K^2 flop) and of a K vector (2 K flop);
- one exact KxK solve per entity: Cholesky K^3/3 plus two triangular solves
  2 K^2;
- bytes: per edge and side one gathered factor row (K x ``gather_bytes``, 2 for
  the bf16 operands the configurations state), and per entity one write and
  one read of its KxK float32 matrix.
"""

from __future__ import annotations


def als_cost(edges: int, users: int, items: int, rank: int, iterations: int,
             gather_bytes: int = 2) -> dict:
    K = rank
    entities = users + items
    flops_iter = 2 * edges * (2 * K * K + 2 * K) + entities * (K ** 3 / 3 + 2 * K * K)
    bytes_iter = 2 * edges * K * gather_bytes + 2 * entities * K * K * 4
    return {"flops": float(flops_iter * iterations),
            "bytes": float(bytes_iter * iterations)}


def least_seconds(cost: dict, peak: dict) -> dict:
    """The least time the chip could take, and which roof sets it."""
    t_flops = cost["flops"] / peak["flops_per_s"]
    t_bytes = cost["bytes"] / peak["bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    return {"seconds": max(t_flops, t_bytes), "bound": bound,
            "t_flops": t_flops, "t_bytes": t_bytes}
