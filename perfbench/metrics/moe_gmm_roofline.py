"""The grouped expert matmul's share of its roofline, in percent: the
least time the chip could take for a step's calls (``expert_gemms`` of the
configuration's FLOP file, at the expected routed rows; for each, the
larger of operations over peak FLOP/s and bytes over peak bytes/s, from
``kernels/moe_gmm.py``) over the calls' device time."""

import harness


def read(r):
    s = harness.kernel_seconds(r, "moe_gmm")
    flops = r.cell.config_module(".flops.py")
    if s is None or not hasattr(flops, "expert_gemms"):
        return None
    cost = harness.load_py(harness.bench_file("kernels", "moe_gmm.py")).cost
    floor = 0.0
    for call in flops.expert_gemms(r.cell.config, r.cell.traffic):
        ops, nbytes = cost(call)
        floor += call["count"] * max(ops / r.peaks["flops_per_s"],
                                     nbytes / r.peaks["hbm_bytes_per_s"])
    return floor * r.records["steps"] / s * 100.0
