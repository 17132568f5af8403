"""The comparison that decides ``correct``: what the timed path produced in
its first steps against the plain reference following the same steps from the
same weights, batches and keys.

Numbers (each against a limit of its own from ``limits/<workload>.json``; a
number the cell's record cannot give, or that the limits file does not name,
is not compared):

``loss_gap``    worst step of |L_program - L_reference| / |L_reference| over
                the compared steps' logged loss (a scanned epoch logs the mean
                of its steps: one number).
``grad_gap``    the first micro-batch's gradient as the optimizer got it
                (MultiSteps' accumulator after one micro-step), by the worst
                leaf: | ||g_p|| - ||g_r|| | / max(||g_r||, median leaf ||g_r||).
``moment_gap``  Adam's first moment after the compared steps, same measure:
                the decayed sum of the gradients the optimizer got. The only
                view of them a scanned epoch leaves.
``change_gap``  the parameters' change over the compared steps, same measure,
                over the leaves whose reference gradient is not nought to
                rounding (at least a thousandth of the median leaf's).
``grad_diff``, ``change_diff``
                the first and the last of these quantities by
                ||p - r|| / ||r|| over all leaves laid end to end: where the
                gap of norms cannot tell a planted fault from rounding, the
                direction can.
``moment_diff`` the direction-aware view of Adam's first moment: per leaf
                ||mu_p - mu_r|| / B, and of these the median leaf's, where
                B = 0.1 * sum_k 0.9^(n-k) ||g_k|| is what the leaf's moment
                would be in the reference had its n accumulated, clipped
                gradients g_k not cancelled (the triangle bound of ``mu``,
                from ``follow``'s ``update_norms``). Two reasons, both read
                on the chip (PERF.md section 4). Against ||mu_r|| itself the
                number fails sound runs: three gradients that nearly cancel
                leave a moment 4 to 7 times smaller than B on about one seed
                in twenty, while the program's error stays what it is. And
                over all leaves laid end to end it is a number of four
                64-element coordinate heads, which hold three quarters and
                more of ||mu||^2 and of the difference and swing together
                (6 to 8 times the usual on about one seed in thirty); the
                median gives every leaf one vote, and a lower precision or
                rows left out move every leaf.
"""

from __future__ import annotations

import numpy as np


def _norms(tree: dict) -> dict:
    return {k: float(np.sqrt(np.sum(np.asarray(v, np.float64) ** 2))) for k, v in tree.items()}


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """Per leaf, the gap between the program's norm and the reference's,
    measured against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    np_, nr = _norms(prog), _norms(ref)
    names = [k for k in nr if keep is None or k in keep]
    med = float(np.median([nr[k] for k in names]))
    out = {}
    for k in names:
        gap = abs(np_[k] - nr[k]) / max(nr[k], med, 1e-300)
        out[k] = gap if np.isfinite(gap) else float("inf")
    return out


def leaf_gap(prog: dict, ref: dict, keep=None):
    """(worst gap, its leaf) of ``leaf_gaps``."""
    gaps = leaf_gaps(prog, ref, keep)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def whole_parts(prog: dict, ref: dict, keep=None) -> tuple:
    """(||p - r||, ||r||) over all (kept) leaves laid end to end."""
    names = [k for k in ref if keep is None or k in keep]
    d2 = sum(float(np.sum((np.asarray(prog[k], np.float64) - np.asarray(ref[k], np.float64)) ** 2))
             for k in names)
    r2 = sum(float(np.sum(np.asarray(ref[k], np.float64) ** 2)) for k in names)
    return float(np.sqrt(d2)), float(np.sqrt(r2))


def whole_diff(prog: dict, ref: dict, keep=None) -> float:
    """||p - r|| / ||r|| over all (kept) leaves laid end to end: the
    direction-aware companion of the gap of norms. Leaving rows out of the
    loss moves a gradient's direction far more than its norm."""
    d, r = whole_parts(prog, ref, keep)
    out = d / max(r, 1e-150)
    return out if np.isfinite(out) else float("inf")


def moment_bounds(update_norms: dict) -> dict:
    """Per leaf, what the norm of Adam's first moment would be after these
    updates had the leaf's gradients all pointed one way:
    0.1 * sum_k 0.9^(n-k) ||g_k||. ``update_norms``: per leaf, the norm of
    each update's gradient as the reference's Adam got it."""
    out = {}
    for k, v in update_norms.items():
        g = np.asarray(v, np.float64)
        out[k] = float(0.1 * np.sum(0.9 ** np.arange(len(g) - 1, -1, -1) * g))
    return out


def moment_diffs(prog_mu: dict, ref_mu: dict, update_norms: dict) -> dict:
    """Per leaf ||mu_p - mu_r|| / B with B of ``moment_bounds``; a leaf that
    never had a gradient reads 0 where the program's moment is 0 too."""
    out = {}
    for k, bound in moment_bounds(update_norms).items():
        d = float(np.linalg.norm(np.asarray(prog_mu[k], np.float64) - np.asarray(ref_mu[k], np.float64)))
        gap = d / bound if bound > 0 else (0.0 if d == 0 else float("inf"))
        out[k] = gap if np.isfinite(gap) else float("inf")
    return out


def moving_leaves(ref_grad: dict) -> set:
    """Leaves whose reference gradient is at least a thousandth of the median
    leaf's: the others move under Adam by round-off alone."""
    n = _norms(ref_grad)
    med = float(np.median(list(n.values())))
    return {k for k, v in n.items() if v >= 1e-3 * med}


def numbers(prog: dict, ref: dict) -> dict:
    """Every number the two records allow, as ``{name: (value, detail)}``."""
    out = {}
    lp, lr = np.asarray(prog["loss"], np.float64), np.asarray(ref["loss"], np.float64)
    if lp.shape != lr.shape:          # a scanned epoch: mean of its steps
        lr = np.asarray([lr.mean()])
    gaps = np.abs(lp - lr) / np.abs(lr)
    gaps = np.where(np.isfinite(gaps), gaps, np.inf)
    out["loss_gap"] = (float(gaps.max()), f"step {int(gaps.argmax())}")
    if prog.get("grad") is not None:
        out["grad_gap"] = leaf_gap(prog["grad"], ref["grad_first"])
        out["grad_diff"] = (whole_diff(prog["grad"], ref["grad_first"]), "all leaves")
    if prog.get("mu") is not None:
        out["moment_gap"] = leaf_gap(prog["mu"], ref["mu"])
        diffs = moment_diffs(prog["mu"], ref["mu"], ref["update_norms"])
        out["moment_diff"] = (float(np.median(list(diffs.values()))),
                              f"median of {len(diffs)} leaves")
    keep = moving_leaves(ref["grad_first"])
    delta = lambda rec, w0: {k: np.asarray(rec[k], np.float64) - w0[k] for k in rec}
    dp, dr = delta(prog["w"], prog["w0"]), delta(ref["w"], prog["w0"])
    out["change_gap"] = leaf_gap(dp, dr, keep)
    out["change_diff"] = (whole_diff(dp, dr, keep), "moving leaves")
    return out


def decide(nums: dict, limits: dict):
    """(correct, compared) where ``compared`` is ``{name: {value, limit,
    worst}}`` for the numbers the limits file names."""
    compared, ok = {}, True
    for name, limit in limits.items():
        if name not in nums:
            raise KeyError(f"limit for {name!r} but the cell's record gives no such number")
        value, detail = nums[name]
        compared[name] = {"value": value, "limit": limit, "worst": detail}
        ok = ok and bool(value <= limit)
    return ok, compared


def reference_record(inputs: dict, w0: dict, half: bool = False, mlp_mantissa=None) -> dict:
    """The plain reference of the configuration's family (``family.py``)
    through the first steps."""
    from benchmarks import family

    return family.of(inputs["model"]).reference.follow(
        w0, inputs["model"], inputs["train"], inputs["batches"], inputs["block"], half=half,
        mlp_mantissa=mlp_mantissa, edge_block=inputs.get("edge_block"))
