"""The numbers ``correct`` is decided on, for a training cell.

Both the program and the reference start from the same weights and run
the same checked rounds on the same feed.  A trajectory is the list of
weights ``x_0 .. x_n`` (host trees, one per round boundary) and the loss
each round reported.  Compared, each as a relative gap:

* ``loss_gap``: the worst round's ``|loss - loss_ref| / |loss_ref|``;
* ``grad_gap``: the first round's aggregated update ``x_0 - x_1`` (the
  gradient the server-side step applies, times ``theta * eta``), by the
  worst leaf: ``| |u_leaf| - |u_ref_leaf| |`` over the larger of
  ``|u_ref_leaf|`` and the median leaf's ``|u_ref|``;
* ``change_gap``: the same of ``x_n - x_0`` after the checked rounds.
  Leaves whose reference update is under a thousandth of the median
  leaf's are left out of it: round-off alone moves them;
* ``grad_angle``: the angle between the first round's update and the
  reference's, over all parameters.  Norms miss a fault that keeps an
  update's size and turns it, as a mini-batch cut in half does.

Where the reference is also run one round from each of the program's own
round boundaries (``compare_steps``), each round is judged alone, so a
fault that first shows in a later round fails it, and the run's own
divergence over the rounds does not add up:

* ``step_gap``: the worst round's update ``x_{r+1} - x_r`` against the
  reference's from the same ``x_r``, by the worst leaf as ``grad_gap``
  (whose round it includes);
* ``step_angle``: the worst round's angle between those two updates;
* ``step_loss_gap``: the worst round's relative gap of the loss.

A cell's ``bench/limits/<workload>.json`` names the numbers it compares.
"""
from __future__ import annotations

import numpy as np


def leaf_norms(a: dict, b: dict) -> dict:
    """Per-leaf Frobenius norm of ``a - b`` (flat dicts of host arrays)."""
    return {k: float(np.linalg.norm(np.asarray(a[k], np.float64)
                                    - np.asarray(b[k], np.float64)))
            for k in a}


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    med = float(np.median(list(ref.values())))
    keys = [k for k in ref if keep is None or k in keep]
    if not keys or not np.isfinite(med):
        return float("inf")
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def leaf_gaps(prog: dict, ref: dict, step: int) -> dict:
    """Per-leaf gap of ``x_step - x_0`` (the terms ``compare`` takes the
    worst of), for a look at which leaf reads high."""
    p = leaf_norms(prog["x"][step], prog["x"][0])
    r = leaf_norms(ref["x"][step], ref["x"][0])
    med = float(np.median(list(r.values())))
    return {k: abs(p[k] - r[k]) / max(r[k], med) for k in r}


def angle(a: dict, b: dict) -> float:
    """The angle, in radians, between two updates flattened over all
    leaves (stable for small angles)."""
    u = np.concatenate([np.ravel(np.asarray(a[k], np.float64)) for k in b])
    v = np.concatenate([np.ravel(np.asarray(b[k], np.float64)) for k in b])
    nu, nv = np.linalg.norm(u), np.linalg.norm(v)
    if nu == 0 or nv == 0:              # no update has no direction
        return float("inf")
    u, v = u / nu, v / nv
    return float(2.0 * np.arctan2(np.linalg.norm(u - v), np.linalg.norm(u + v)))


def compare(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: ``{"x": [x_0 .. x_n], "loss": [l_0 .. l_{n-1}]}``
    with each ``x_i`` a flat dict of host arrays."""
    loss_gap = float(max(abs(a - b) / abs(b)
                         for a, b in zip(prog["loss"], ref["loss"])))
    if not np.isfinite(loss_gap):
        loss_gap = float("inf")
    g_prog = leaf_norms(prog["x"][1], prog["x"][0])
    g_ref = leaf_norms(ref["x"][1], ref["x"][0])
    med = float(np.median(list(g_ref.values())))
    keep = {k for k, v in g_ref.items() if v >= 1e-3 * med}
    c_prog = leaf_norms(prog["x"][-1], prog["x"][0])
    c_ref = leaf_norms(ref["x"][-1], ref["x"][0])
    step = {k: prog["x"][1][k] - prog["x"][0][k] for k in prog["x"][0]}
    step_ref = {k: ref["x"][1][k] - ref["x"][0][k] for k in ref["x"][0]}
    out = {"loss_gap": loss_gap,
           "grad_gap": worst_leaf_gap(g_prog, g_ref),
           "change_gap": worst_leaf_gap(c_prog, c_ref, keep),
           "grad_angle": angle(step, step_ref)}
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def compare_steps(prog: dict, steps: list) -> dict:
    """``prog`` as for :func:`compare`; ``steps[r]``: ``{"x": x, "loss":
    l}``, the reference's round from the program's ``x_r``."""
    gaps, angles, losses = [], [], []
    for r, st in enumerate(steps):
        x_r = prog["x"][r]
        gaps.append(worst_leaf_gap(leaf_norms(prog["x"][r + 1], x_r),
                                   leaf_norms(st["x"], x_r)))
        angles.append(angle(
            {k: prog["x"][r + 1][k] - x_r[k] for k in x_r},
            {k: st["x"][k] - x_r[k] for k in x_r}))
        losses.append(abs(prog["loss"][r] - st["loss"]) / abs(st["loss"]))
    out = {"grad_gap": gaps[0], "step_gap": max(gaps),
           "step_angle": max(angles), "step_loss_gap": max(losses)}
    return {k: (float(v) if np.isfinite(v) else float("inf"))
            for k, v in out.items()}


def flatten(tree, prefix="") -> dict:
    """Nested dict of arrays -> ``{"a.b.c": host array}``."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, name + "."))
        else:
            out[name] = np.asarray(v)
    return out


def unflatten(flat: dict) -> dict:
    """``{"a.b.c": array}`` -> nested dict (the inverse of ``flatten``)."""
    out = {}
    for name, v in flat.items():
        *path, leaf = name.split(".")
        node = out
        for k in path:
            node = node.setdefault(k, {})
        node[leaf] = v
    return out
