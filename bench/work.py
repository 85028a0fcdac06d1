"""Logical work of the kernels the benchmark holds to their roofline.

Each function counts the bytes a kernel call must move and the operations
it must do for the problem as the request states it (``m`` agents, ``d``
dimensions, ``k`` components, ``K`` gossip rounds), in float32, and not the
padded arrays an implementation happens to use.  So a change that removes
padding raises the share instead of shrinking the yardstick.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

F32 = 4


class Work(NamedTuple):
    flops: float
    bytes: float

    def __mul__(self, n: float) -> "Work":
        return Work(self.flops * n, self.bytes * n)

    __rmul__ = __mul__

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_s(self, peaks: Dict[str, float]) -> float:
        """The least time the chip needs: the larger of the compute and the
        memory bound."""
        return max(self.flops / peaks["flops_per_s"],
                   self.bytes / peaks["hbm_bytes_per_s"])

    def bound(self, peaks: Dict[str, float]) -> str:
        return ("compute" if self.flops / peaks["flops_per_s"]
                >= self.bytes / peaks["hbm_bytes_per_s"] else "bytes")


def fastmix_track(m: int, d: int, k: int, K: int) -> Work:
    """One tracking + K-round FastMix call (``_fastmix_track_kernel``) on
    one problem: read S, G, G_prev and write the mixed S, each ``(m, d*k)``;
    2 operations per element for Eqn. 3.1, then per round an ``(m, m)`` by
    ``(m, d*k)`` product and 3 operations per element for the Chebyshev
    combine."""
    n = d * k
    return Work(flops=2 * m * n + K * (2 * m * m * n + 3 * m * n),
                bytes=4 * m * n * F32)


def gram(d: int, k: int) -> Work:
    """One Gram ``X^T X`` of an agent's ``(d, k)`` factor (``_gram_kernel``):
    read X once, write the ``(k, k)`` result."""
    return Work(flops=2 * d * k * k, bytes=(d * k + k * k) * F32)


def gram_per_iteration(m: int, d: int, k: int) -> Work:
    """CholeskyQR2 forms two Grams per agent per power iteration."""
    return 2 * m * gram(d, k)


def roofline_share(work: Work, kernel_s: float, peaks: Dict[str, float]):
    """Percent of the roofline: least time over the measured kernel time;
    ``None`` where the kernel did not run."""
    if kernel_s <= 0:
        return None
    return 100.0 * work.least_s(peaks) / kernel_s
