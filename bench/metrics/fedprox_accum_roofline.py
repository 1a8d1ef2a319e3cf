"""Kernels: the eq.-10 kernel (``fedprox_accum_2d``) as a share of its
roofline: the least time its calls in the trace could take (bytes over
HBM bandwidth, or operations over peak, whichever is larger; counted from
the call's plane shapes) over their device time, in percent."""
import costs
import roofline

KERNELS = ("fedprox_accum_2d",)


def flops(results, operands):
    """Per element of the ``(G, R, LANE)`` stack ``x_new``:
    ``x - eta*(g + mu*(x - a))`` is 5, ``acc + c*active*g`` is 3."""
    return 8 * costs.elems(results[0][1])


def read(run):
    return roofline.share(run, KERNELS, flops)
