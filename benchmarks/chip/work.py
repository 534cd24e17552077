"""Operations and bytes the served work needs, computed from shapes.

These counts, with each architecture module's `shape` (`arch/`), are the
yardstick of every roofline share and MFU the benchmark reports. They count
the work the algorithm needs, not what an implementation happens to do: a
skipped tile is not work done, and a weight copy the implementation makes
is not work needed.
"""

from __future__ import annotations

import dataclasses

from chip.peaks import Peak

BF16, INT8, F32 = 2, 1, 4


@dataclasses.dataclass(frozen=True)
class Job:
    flops: float
    bytes: float

    def least_seconds(self, peak: Peak) -> float:
        """The least time the chip could take: the larger of the operations
        over peak FLOP/s and the bytes over peak bandwidth."""
        return max(self.flops / peak.bf16_flops,
                   self.bytes / peak.hbm_bytes_per_s)


def reuse_site_job(m: int, k: int, n: int, live: float) -> Job:
    """One reuse-site call, O = O_prev + delta . W, for M rows:

    read x (bf16) and prev_q (int8), write the new codes (int8); read the
    live share of the weight tiles (bf16); read prev_out (f32) and write the
    output (f32); 2.M.K.N.live operations."""
    return Job(
        flops=2.0 * m * k * n * live,
        bytes=(BF16 * m * k + INT8 * m * k + INT8 * m * k
               + BF16 * k * n * live + F32 * m * n + F32 * m * n),
    )


def reuse_gemm_job(m: int, k: int, n: int, live: float) -> Job:
    """The GEMM half of a reuse-site call alone: read the delta (bf16) and
    the live weight tiles (bf16), read prev_out and write the output (f32)."""
    return Job(
        flops=2.0 * m * k * n * live,
        bytes=BF16 * m * k + BF16 * k * n * live + F32 * m * n + F32 * m * n,
    )
