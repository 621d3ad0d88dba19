"""The numbers that decide ``correct``, each computed from the program's
readings and the plain reference's; a number counts where the cell's file
(``bench/workloads/<cell>.json``) gives it a limit.

Training (three steps of the timed step, from the same weights and rows;
every reading is on the host): the program's first clipped gradient is
read from AdamW's first moment after one step (m_1 / (1 - b1)), its
parameters after the third step.  Layer leaves count once per layer, and
every leaf gap is taken over the larger of the reference leaf's norm and
the median leaf's:

- ``loss_gap``: the worst step's |loss - reference loss| / reference loss;
- ``grad_gap``: the worst leaf's gap between the norms of the program's
  and the reference's first clipped gradient;
- ``change_gap``: the same for the change of the parameters over the
  three steps, over the leaves whose reference gradient is at least a
  thousandth of the median leaf's (a smaller one moves under AdamW by
  round-off alone);
- ``grad_err``: the worst leaf's norm of the difference between the two
  first clipped gradients, element by element;
- ``change_err``: the same for the parameters after the three steps (the
  difference of the changes), over the leaves ``change_gap`` keeps.

Norms of leaves hide errors that average out (a lower precision rounds
each element, not the mean); the element-wise numbers see them.

Serving: ``max_gap``, the widest gap by which a served greedy token's
logit lies below the reference's best logit at its position.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

GRAD_FLOOR = 1e-3
CHUNK = 1 << 22                 # elements per task, bounds host temporaries
THREADS = min(8, os.cpu_count() or 1)


def _sq(x, y=None) -> float:
    """Sum of squares of ``x`` (or of ``x - y``) in float32 chunks, on a
    few threads (numpy releases the interpreter lock in each)."""
    x = np.ravel(x)
    y = None if y is None else np.ravel(y)

    def chunk(lo):
        d = x[lo:lo + CHUNK].astype(np.float32)
        if y is not None:
            d -= y[lo:lo + CHUNK].astype(np.float32)
        return float(np.dot(d, d))

    with ThreadPoolExecutor(THREADS) as pool:
        return sum(pool.map(chunk, range(0, x.size, CHUNK)))


def leaf_norms(tree: dict, minus: dict | None = None) -> dict:
    """Per-leaf L2 norms of ``tree`` (or of ``tree - minus``), host arrays
    in the reference's layout; layer leaves give one norm per layer."""
    out = {}
    for name, x in tree.items():
        sub = None if minus is None else minus[name]
        if name == "layers":
            for n, y in x.items():
                out[n] = np.sqrt([_sq(y[i], None if sub is None else sub[n][i])
                                  for i in range(y.shape[0])])
        else:
            out[name] = np.sqrt([_sq(x, sub)])
    return out


def _flat(norms: dict):
    return {(k, i): float(x) for k, v in norms.items()
            for i, x in enumerate(np.ravel(v))}


def _scaled(values: dict, ref: dict, keep=None, gap: bool = True):
    """{leaf: |value - ref| / max(ref, median ref)} (``gap``) or
    {leaf: value / max(ref, median ref)}, over the leaves in ``keep``."""
    v, r = _flat(values), _flat(ref)
    if set(v) != set(r):
        raise ValueError(f"leaves differ: {sorted(set(v) ^ set(r))}")
    keys = [k for k in r if keep is None or k in keep]
    med = float(np.median([r[k] for k in keys]))
    return {k: (abs(v[k] - r[k]) if gap else v[k]) / max(r[k], med)
            for k in keys}


def movable(ref_grad_norms: dict) -> set:
    """Leaves whose reference gradient is not nought to rounding."""
    r = _flat(ref_grad_norms)
    med = float(np.median(list(r.values())))
    return {k for k, x in r.items() if x >= GRAD_FLOOR * med}


def reference_norms(ref: dict, start: dict) -> dict:
    """The reference's leaf norms that every comparison divides by; kept
    in ``ref`` so that each is computed once."""
    if "norms" not in ref:
        grad = leaf_norms(ref["grad"])
        ref["norms"] = {"grad": grad,
                        "change": leaf_norms(ref["params"], start),
                        "keep": movable(grad)}
    return ref["norms"]


def training_leaves(prog: dict, ref: dict, start: dict) -> dict:
    """Every leaf's reading of each training number but ``loss_gap``.
    ``prog`` and ``ref``: {"losses", "grad", "params"} as in
    ``reference.follow_training``; ``start``: the weights both began
    from."""
    rn = reference_norms(ref, start)
    return {
        "grad_gap": _scaled(leaf_norms(prog["grad"]), rn["grad"]),
        "change_gap": _scaled(leaf_norms(prog["params"], start),
                              rn["change"], rn["keep"]),
        "grad_err": _scaled(leaf_norms(prog["grad"], ref["grad"]),
                            rn["grad"], gap=False),
        "change_err": _scaled(leaf_norms(prog["params"], ref["params"]),
                              rn["change"], rn["keep"], gap=False),
    }


def training_numbers(prog: dict, ref: dict, start: dict, leaves=None) -> dict:
    leaves = leaves or training_leaves(prog, ref, start)
    losses = zip(prog["losses"], ref["losses"], strict=True)
    numbers = {"loss_gap": max(abs(p - r) / abs(r) for p, r in losses)}
    numbers.update({name: max(v.values()) for name, v in leaves.items()})
    return numbers


def worst(leaves: dict) -> dict:
    """The leaf at which each number is reached: {number: "leaf[layer]"}."""
    return {name: "{}[{}]".format(*max(v, key=v.get))
            for name, v in leaves.items()}
