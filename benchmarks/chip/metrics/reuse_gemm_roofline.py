"""The reuse GEMM kernels' share of their roofline, in percent: the least
time of the GEMM half of every site call in the traced window's decode
steps (`work.reuse_gemm_job`: delta and live weight tiles in, prev_out in,
output out) over the device time of the `reuse_matmul_*` kernels alone."""

from chip import work
from chip.metrics.reuse_site_roofline import site_share


def read(ctx):
    return site_share(ctx, work.reuse_gemm_job,
                      lambda name, scope: "/reuse_matmul_" in scope
                      and scope.endswith("/pallas_call"))
