"""The comparison that decides ``correct``: what the timed path produced,
against the plain reference that the configuration names (its
``reference`` module, ``cell.arch.reference``).

Readings, each judged against the configuration's limit of the same name:

* ``serve_gap``: over a sample of the requests the window finished (the
  longest, and others drawn from the seed: ``check.sample_requests`` in
  all, every served token of each), the widest gap (in logits) by which the
  reference logit of a served token lies below the reference's best at
  that position.
* ``ft_loss_gap``: worst relative gap between the program's and the
  reference's loss, over the iterations compared.
* ``ft_grad_gap``: the first iteration's gradient as the optimizer got it
  (read from AdamW's first moment after one step, m1 / (1 - b1)): worst
  leaf's gap between the program's and the reference's norm, over the larger
  of the reference leaf's norm and the median leaf's.
* ``ft_change_gap``: the same for the norm of each leaf's change over the
  iterations compared.

Leaves whose reference gradient is under a thousandth of the median leaf's
in every step (LoRA A at step 1, whose gradient is 0 while B is 0) move by
round-off alone and are left out of both leaf readings.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

NEGLIGIBLE = 1e-3


def sample_requests(done: List, seed: int, count: int) -> List:
    """The finished request with the most served tokens, then others drawn
    from the seed, ``count`` in all (every one, where fewer finished)."""
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.served), r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 11]).permutation(len(rest))
    return [longest] + [rest[i] for i in order[:count - 1]]


def _bucket(n: int, step: int = 1024) -> int:
    return -(-n // step) * step


def serve_gaps(R, dm, params, reqs: List, lowp: Optional[str] = None
               ) -> np.ndarray:
    """Gap of every served token of ``reqs`` (prompt, then served tokens
    teacher-forced), one request at a time, padded to 1024-token buckets,
    by the reference module ``R``."""
    out = []
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.served[:-1], np.int32)])
        n_sel = len(r.served)
        tokens = np.zeros((_bucket(len(seq)),), np.int32)
        tokens[:len(seq)] = seq
        rows = np.full((1024,), len(r.prompt) - 1, np.int32)
        rows[:n_sel] = len(r.prompt) - 1 + np.arange(n_sel)
        served = np.zeros((1024,), np.int32)
        served[:n_sel] = r.served
        g = R.served_gaps(dm, params, jnp.asarray(tokens), jnp.asarray(rows),
                          jnp.asarray(served), lowp)
        out.append(np.asarray(g)[:n_sel])
    return np.concatenate(out) if out else np.zeros((0,))


def _leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(jnp.linalg.norm(
        jnp.asarray(x, jnp.float32))) for p, x in flat}


def _worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
                keep: List[str]) -> float:
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def program_trace(snap: Dict, n: int, opt: Dict):
    """What the program left, in the shape ``reference.train`` returns:
    losses, the first step's clipped gradient (AdamW's m1 / (1 - b1)),
    and the adapters after iteration n."""
    g1 = jax.tree.map(lambda m: jnp.asarray(m) / (1 - opt["b1"]), snap["m1"])
    return snap["losses"][:n], g1, None, snap["adapters"]


def ft_compare(ref, cand, adapters0) -> Dict:
    """Readings of a candidate (the program, or the reference changed to
    stand in its place) against the reference, both as ``train`` returns
    them."""
    ref_losses, ref_g1, ref_gn, ref_ad = ref
    p_losses, p_g1, _, p_ad = cand
    per_step = [{jax.tree_util.keystr(p): x for p, x in
                 jax.tree_util.tree_flatten_with_path(s)[0]} for s in ref_gn]
    names = list(per_step[0])
    med = [float(np.median(list(s.values()))) for s in per_step]
    moved = [k for k in names
             if any(s[k] >= NEGLIGIBLE * m for s, m in zip(per_step, med))]
    first = [k for k in names if per_step[0][k] >= NEGLIGIBLE * med[0]]
    change = lambda ad: _leaf_norms(jax.tree.map(
        lambda a, b: jnp.asarray(a, jnp.float32) - jnp.asarray(b, jnp.float32),
        ad, adapters0))
    return {
        "ft_loss_gap": max(abs(p - r) / abs(r)
                           for p, r in zip(p_losses, ref_losses)),
        "ft_grad_gap": _worst_leaf(_leaf_norms(p_g1), _leaf_norms(ref_g1),
                                   first),
        "ft_change_gap": _worst_leaf(change(p_ad), change(ref_ad), moved),
        "ref_losses": list(ref_losses), "cand_losses": list(p_losses),
        "leaves_compared": len(moved), "leaves": len(names),
    }


def judge(readings: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    """Names of the readings over their limit (or missing)."""
    return [k for k, lim in limits.items()
            if k not in readings or not readings[k] <= lim]


# ------------------------------------------------- readings of one run --
def serving_sample(cell, rec) -> List:
    """The seeded sample of the requests the window finished."""
    done = [r for r in cell.reqs
            if r.finished is not None and rec["w0"] <= r.finished <= rec["w1"]
            and not r.failed]
    return sample_requests(done, cell.seed,
                           cell.conf["check"]["sample_requests"])


def serve_reading(cell, dm, params, sample: List, lowp=None):
    """(widest gap over the sample, rids whose own widest gap is over the
    limit), given the serving weights."""
    limit = cell.conf["limits"]["serve_gap"]
    worst, wrong = 0.0, set()
    for r in sample:
        g = float(serve_gaps(cell.arch.reference, dm, params, [r],
                             lowp).max())
        worst = max(worst, g)
        if not g <= limit:
            wrong.add(r.rid)
    return worst, wrong


def reference_train(cell, dm, params, lowp=None, rows=None):
    """The reference over the micro-batches the program's iterations
    compared took, optionally in low precision or on some rows only."""
    return cell.arch.reference.train(dm, cell.conf["finetune"]["optimizer"], params,
                   cell.adapters0, cell.data, cell.pc.accum,
                   cell.check_iters, lowp=lowp, rows=rows)


def finetune_reading(cell, dm, params) -> Optional[Dict]:
    """The program's iterations against the reference, given the finetune
    weights; None when fewer iterations ran than the check compares."""
    n = cell.check_iters
    if len(cell.snapshots["losses"]) < n:
        return None
    ref = reference_train(cell, dm, params)
    prog = program_trace(cell.snapshots, n, cell.conf["finetune"]["optimizer"])
    return ft_compare(ref, prog, cell.adapters0)
