"""The numbers that decide `correct`, and their limits.

Training (the program's first rounds against the reference's):
  loss_gap    the worst round's |loss - reference loss| / |reference loss|
  grad_gap    the first gradient as the optimizer got it (Adam's first
              moment after one step, / (1 - beta1)), by the worst leaf:
              | |g| - |g_ref| | / max(|g_ref|, median leaf's |g_ref|)
  change_gap  the adapters' change over the rounds, by the worst leaf,
              measured the same way; leaves whose reference gradient
              stays under a thousandth of the median leaf's in every round
              are left out (they move by Adam's round-off alone)

Serving (a seeded sample of finished requests, the longest among them):
  served_gap       the widest gap by which a served token's reference
                   logit lies below the reference's best logit at that
                   position
  served_gap_mean  that gap averaged over every served token of the
                   sample (0 where the token is the reference's best)
  served_gap_near  the gaps summed over the sample, over the number of its
                   positions where the reference's best two logits lie
                   within NEAR of each other: only near ties can flip

Limits live in limits/<workload>.json.  The numbers compared are those
that file names; each passes when it is at or under its limit, and a
number the run did not produce fails.  Any other number is printed
beside them for the record.
"""

from __future__ import annotations

import json

import numpy as np

from chipbench.harness import HERE


def _flat(tree, prefix=""):
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(_flat(tree[k], f"{prefix}{k}/"))
        return out
    return {prefix[:-1]: np.asarray(tree, np.float64)}


def leaf_norms(tree):
    return {k: float(np.linalg.norm(v)) for k, v in _flat(tree).items()}


def worst_leaf_gap(prog, ref, keep=None):
    """max over leaves of | |p| - |r| | / max(|r|, median |r|)."""
    pn, rn = leaf_norms(prog), leaf_norms(ref)
    names = [k for k in rn if keep is None or k in keep]
    med = float(np.median([rn[k] for k in names])) if names else 0.0
    worst, where = 0.0, None
    for k in names:
        g = abs(pn[k] - rn[k]) / max(rn[k], med, 1e-30)
        if g > worst:
            worst, where = g, k
    return worst, where


def train_numbers(prog, ref, beta1=0.9):
    losses = np.asarray(prog["losses"], np.float64)
    rl = np.asarray(ref["losses"], np.float64)
    loss_gap = float(np.max(np.abs(losses - rl) / np.abs(rl)))
    g_prog = _scale(prog["m1"], 1.0 / (1.0 - beta1))
    grad_gap, g_where = worst_leaf_gap(g_prog, ref["g1"])
    # leaves the reference moves: gradient above 1e-3 of the median
    # leaf's in at least one round
    moved = set()
    for norms in ref["grad_norms"]:
        med = float(np.median(list(norms.values())))
        moved |= {k for k, v in norms.items() if v >= 1e-3 * med}
    d_prog = _sub(prog["after"], prog["start"])
    d_ref = _sub(ref["after"], prog["start"])
    change_gap, c_where = worst_leaf_gap(d_prog, d_ref, keep=moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap,
            "_where": {"grad_gap": g_where, "change_gap": c_where,
                       "losses": losses.tolist(),
                       "ref_losses": rl.tolist()}}


def _scale(tree, s):
    return {k: _scale(v, s) if isinstance(v, dict) else np.asarray(v) * s
            for k, v in tree.items()}


def _sub(a, b):
    return {k: _sub(a[k], b[k]) if isinstance(a[k], dict)
            else np.asarray(a[k], np.float64) - np.asarray(b[k], np.float64)
            for k in a}


NEAR = 0.05      # a near tie: the reference's best two logits this close


def serve_numbers(readout):
    """served_gap, served_gap_mean and served_gap_near over a readout
    {rid: {"gap": per-token gaps, "margin": the reference's best minus
    its second best logit at each token}}."""
    gap = np.concatenate([np.asarray(v["gap"], np.float64)
                          for v in readout.values()] or [np.full(1, np.inf)])
    margin = np.concatenate([np.asarray(v["margin"], np.float64)
                             for v in readout.values()] or [np.zeros(1)])
    return {"served_gap": float(gap.max()),
            "served_gap_mean": float(gap.mean()),
            "served_gap_near": float(gap.sum() / max(1, (margin < NEAR).sum()))}


def load_limits(workload):
    path = HERE / "limits" / f"{workload}.json"
    if not path.exists():
        return None
    with open(path) as f:
        return json.load(f)["limits"]


def judge(numbers, limits):
    """(correct, [(name, number, limit)]): the numbers that `limits`
    names, each beside its limit, then the others with limit None."""
    limits = limits or {}
    rows = [(k, numbers.get(k, np.inf), lim) for k, lim in limits.items()]
    rows += [(k, v, None) for k, v in numbers.items()
             if not k.startswith("_") and k not in limits]
    ok = bool(limits) and all(
        np.isfinite(v) and v <= lim for _, v, lim in rows if lim is not None)
    return ok, rows
