"""The DT-score Pallas kernel's share of its roofline: the least time the
chip could take for its calls (the larger of operations over the bf16
peak and bytes over the HBM bandwidth, counted from the candidate tiles)
over the device time of those calls in the trace. None when the trace
holds no call of the kernel."""
from chipbench.flops import veds_score_cost
from chipbench.trace import op_events


def is_kernel(name: str, op: str) -> bool:
    return "veds_dt_score" in op or "veds_dt_score" in name


def read(run):
    calls = op_events(run.raw, run.span, is_kernel)
    if not calls:
        return None
    cost = veds_score_cost(run.traffic["cells"] * run.cfg["n_sov"])
    least = max(cost["flops"] / run.peaks["bf16_flops_per_s"],
                cost["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(calls) / (sum(c[1] for c in calls) * 1e-9)
