"""A kernel's share of its roofline over the calls a trace holds."""
import costs


def share(run, kernels, flops):
    """Sum of the least times over sum of the device times of the traced
    custom calls named in ``kernels``, in percent; None where the trace
    holds no such call.  ``flops(results, operands)`` counts one call's
    arithmetic from the ``(dtype, dims, in_hbm)`` of its arrays."""
    calls = [k for k in run.trace["kernels"] if k["kernel"] in kernels]
    spent = sum(k["ns"] for k in calls) / 1e9
    if not calls or spent <= 0:
        return None
    least = sum(costs.least_seconds(flops(k["results"], k["operands"]),
                                    costs.hbm_bytes(k["results"],
                                                    k["operands"]),
                                    run.peaks)
                for k in calls)
    return 100.0 * least / spent
