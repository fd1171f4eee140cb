"""The comparison that decides ``correct`` for the live FL cells.

A cell's first ``check_steps`` aggregation steps (sync rounds, or fedbuff
merges) run through the timed path during set-up, and the plain reference
replays them from the same weights and data. Local SGD at the program's
learning rate is chaotic on these deep nets: a gap of 3e-4 between two float32
gradients at the first local step grows to 0.3 in the worst leaf by the
fourth (PERF.md, Findings). So the numbers compared are steady ones:

* ``step_loss_gap``: each client's first local step, from the initial
  model: the largest relative gap of its loss;
* ``step_grad_gap``: the first gradient as the optimizer got it, worked
  out from each client's state after that step, ``(p0 - p) / lr``; the
  worst leaf over all clients: the gap between the program's leaf norm
  and the reference's, over the larger of that reference norm and the
  median leaf's, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (nought to rounding: a bias that a
  following normalisation cancels);
* ``loss_gap``: the largest relative gap of the steps' mean client
  loss;
* ``update_gap``: the server's first update ``p1 - p0``, the same gap of
  norms taken for the median leaf;
* ``change_gap``: the same for the change over all the steps ``p3 - p0``,
  leaving out leaves whose first reference update is under a thousandth
  of the median leaf's (their movement is round-off);
* ``hub_weight_gap``: the largest relative gap, over the steps, between
  the weight the hub combined (the example weights its FedAvg averaged in
  a sync round; the effective weight the streaming hub had folded when it
  merged) and the reference's own sum of the example weights of the
  updates that reached the hub, discounted for staleness in fedbuff.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

NOUGHT = 1e-3  # leaves moved under this share of the median leaf


def _norms(a, b=None) -> List[float]:
    """Per-leaf norm of ``a`` (or of ``a - b``) over lists of arrays."""
    if b is None:
        return [float(np.linalg.norm(np.asarray(x, np.float64)))
                for x in a]
    return [float(np.linalg.norm(np.asarray(x, np.float64)
                                 - np.asarray(y, np.float64)))
            for x, y in zip(a, b)]


def leaf_gaps(prog: List[float], ref: List[float], keep=None) -> List[float]:
    """Per leaf: |norm_prog - norm_ref| / max(norm_ref, median ref norm)."""
    med = float(np.median(ref))
    out = []
    for i, (p, r) in enumerate(zip(prog, ref)):
        if keep is not None and not keep[i]:
            continue
        denom = max(r, med)
        out.append(abs(p - r) / denom if denom > 0 else 0.0)
    return out


def _moved(ref_norms: List[float]) -> List[bool]:
    """Leaves the reference moves by more than round-off."""
    med = float(np.median(ref_norms))
    return [r >= NOUGHT * med for r in ref_norms]


def _rel(a, b) -> float:
    return abs(a - b) / abs(b)


def readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog`` and ``ref`` hold: ``step_losses`` (per client),
    ``step_grads`` (per client, a list of leaves), ``losses`` (per
    aggregation step), ``p0``, ``p1``, ``p3`` (lists of leaves, the same
    flattening on both sides) and ``hub_weight`` (per aggregation step)."""
    out = {}
    if len(prog["step_losses"]) != len(ref["step_losses"]) or not len(
            ref["step_losses"]):
        out["step_loss_gap"] = out["step_grad_gap"] = float("inf")
    else:
        out["step_loss_gap"] = float(np.max([_rel(a, b) for a, b in zip(
            prog["step_losses"], ref["step_losses"])]))
        out["step_grad_gap"] = float(np.max([
            np.max(leaf_gaps(_norms(gp), _norms(gr), _moved(_norms(gr))))
            for gp, gr in zip(prog["step_grads"], ref["step_grads"])]))
    if len(prog["losses"]) != len(ref["losses"]):
        out["loss_gap"] = float("inf")
    else:
        out["loss_gap"] = float(np.max([_rel(a, b) for a, b in zip(
            prog["losses"], ref["losses"])]))
    d1p, d1r = _norms(prog["p1"], prog["p0"]), _norms(ref["p1"], ref["p0"])
    d3p, d3r = _norms(prog["p3"], prog["p0"]), _norms(ref["p3"], ref["p0"])
    keep = _moved(d1r)
    out["update_gap"] = float(np.median(leaf_gaps(d1p, d1r)))
    out["change_gap"] = float(np.median(leaf_gaps(d3p, d3r, keep)))
    hp, hr = prog.get("hub_weight"), ref["hub_weight"]
    if hp is None or len(hp) != len(hr) or None in hp:
        out["hub_weight_gap"] = float("inf")
    else:
        out["hub_weight_gap"] = float(np.max([_rel(a, b)
                                              for a, b in zip(hp, hr)]))
    return {k: (v if np.isfinite(v) else float("inf"))
            for k, v in out.items()}


def explain(prog: Dict, ref: Dict, shapes) -> Dict[str, list]:
    """Where each leaf number is worst: [client, leaf, shape, program norm,
    reference norm] for the first gradient; the three widest leaves of
    the first update."""
    out = {}
    if prog["step_grads"] and len(prog["step_grads"]) == len(
            ref["step_grads"]):
        best = None
        for c, (gp, gr) in enumerate(zip(prog["step_grads"],
                                         ref["step_grads"])):
            np_, nr = _norms(gp), _norms(gr)
            keep = _moved(nr)
            gaps = leaf_gaps(np_, nr)
            for i, g in enumerate(gaps):
                if keep[i] and (best is None or g > best[0]):
                    best = (g, [c, i, list(shapes[i]), np_[i], nr[i]])
        out["step_grad_worst"] = best[1] if best else None
    d1p, d1r = _norms(prog["p1"], prog["p0"]), _norms(ref["p1"], ref["p0"])
    gaps = leaf_gaps(d1p, d1r)
    top = np.argsort(gaps)[::-1][:3]
    out["update_widest"] = [[int(i), list(shapes[i]), d1p[i], d1r[i]]
                            for i in top]
    return out


def judge(values: Dict[str, float], limits: Dict[str, float]):
    """-> (correct, [{"name", "value", "limit"}]). A number missing from
    ``values`` or not finite fails."""
    checks, ok = [], True
    for name, limit in limits.items():
        v = values.get(name, float("inf"))
        good = bool(np.isfinite(v) and v <= limit)
        ok = ok and good
        checks.append({"name": name, "value": v, "limit": limit})
    return ok, checks
