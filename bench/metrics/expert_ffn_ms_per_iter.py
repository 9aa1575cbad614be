"""Device time of the routed-expert FFN kernels per loop iteration, in
ms: the durations of the Pallas `expert_gemv` (decode's tier FFNs) and
`moe_gemm` (prefill's grouped GEMMs) kernel events inside the traced
window, over the iterations traced. Source: the device trace."""

# the instruction names the kernels carry into the device trace
# (`%expert_gemv.3 = ... custom-call(...)`)
KERNELS = ("expert_gemv", "moe_gemm")


def read(ctx):
    red = ctx["trace"]
    ns = sum(e - s for name, s, e, _ in red["op_events"]
             if name.lstrip("%").startswith(KERNELS))
    if red["steps"] <= 0 or ns <= 0:
        return None
    return ns / 1e6 / red["steps"]
