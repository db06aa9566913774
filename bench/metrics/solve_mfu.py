"""solve_mfu: a solve's share of the chip's roofline, in %.

The operations and bytes one solve needs (``bench/cost.py``, from the
shapes) give the least time the chips could take: the larger of
operations over peak FLOP/s and bytes over peak HBM bandwidth
(``bench/peaks.py``). That time over the measured time of a solve
(``solve_s``) times the chips used.
"""
from bench import cost, peaks


def read(run):
    if not run.solve_s:
        return None
    flops, nbytes = cost.solve_cost(int(run.config["rows"]),
                                    int(run.config["features"]),
                                    int(run.cell["rounds"]))
    peak = peaks.peaks_of(run.device_kind)
    least = max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / (run.solve_s * run.chips)
