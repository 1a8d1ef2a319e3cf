"""Kernels: the eq.-11 kernels (``nova_aggregate_2d`` and
``nova_aggregate_stacked_2d``) as a share of their roofline, as
``fedprox_accum_roofline`` counts it, in percent."""
import costs
import roofline

KERNELS = ("nova_aggregate_2d", "nova_aggregate_stacked_2d")


def flops(results, operands):
    """The weighted sum over the ``(G, R, LANE)`` stack ``d`` is 2 per
    element; ``x - theta_eta * sum`` is 2 per output element."""
    return 2 * costs.elems(operands[1][1]) + 2 * costs.elems(results[0][1])


def read(run):
    return roofline.share(run, KERNELS, flops)
