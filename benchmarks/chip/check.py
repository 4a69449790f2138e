"""The comparison that decides ``correct``: what the timed path produced,
against the configuration's plain float32 reference (``references/``),
run once the window has closed and the program's state is freed.

Serving: a sample of finished requests drawn from the seed, the one with
the most served tokens always in it.  The reference runs over each prompt
followed by its served tokens, under that request's own task scales, and
reads at each served position how far the served token's logit lies below
the reference's best.  The number compared is the widest such gap.

Training: the first steps' losses, each leaf's first gradient as the
optimizer got it (its first moment after one step, over ``1 − β1``), and
each leaf's change over the checked steps, against the reference's own
AdamW steps on the same rows.  Leaves compare by norm: the gap between the
program's norm and the reference's, over the larger of the reference's
norm of that leaf and of the median leaf.  A leaf whose reference gradient
is under a thousandth of the median leaf's moves by round-off alone and is
left out of the change.

``lowp`` runs the reference in a lower precision in the program's place:
the control, read on the same prompts and served tokens (for serving, the
gap of the token that the lower precision puts first).  The faults a cell
can have are planted the same way, in the reference in the program's
place (serving: another task's scales; training: half of each batch).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import named
import weights


def reference(name: str):
    """The plain reference module ``references/<name>.py``."""
    return named.module("references", name)


def sample(records: list, seed: int, tokens: int, most: int) -> list:
    """Finished requests to compare: the one with the most served tokens,
    then others drawn from the seed until ``tokens`` served tokens or
    ``most`` requests."""
    done = [r for r in records if r.done and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.tokens), r.rid))
    out = [done[0]]
    rng = np.random.default_rng([seed & 0x7FFFFFFF, seed >> 31, 7])
    for i in rng.permutation(len(done) - 1) + 1:
        if sum(len(r.tokens) for r in out) >= tokens or len(out) >= most:
            break
        out.append(done[i])
    return out


def task_scales(ref, arch, root, seed: int, task: int) -> dict:
    """{linear: (L, N, G)} scales of task ``task``: the drawn base scales
    times the task's host multipliers."""
    base = ref.base_scales(arch, root)
    return {name: s * jnp.asarray(weights.task_multiplier(
        seed, task, "layers/" + name, s.shape)) for name, s in base.items()}


def serve_gaps(ref, arch, seed: int, reqs: list, prompts: dict,
               length: int, rows_len: int, lowp=None, task_of=None) -> dict:
    """Widest gap, over ``reqs``' served tokens, between the reference's
    best logit and the served token's.  With ``lowp`` or ``task_of`` the
    reference stands in the program's place, computed in precision
    ``lowp`` (the control) or under the scales of task ``task_of(r)`` (a
    fault), and the gap is that of the token it puts first.  Sequences are
    padded to ``length`` and served positions to ``rows_len``, so the
    reference compiles once a cell."""
    root = weights.root_key(seed)
    stand_in = lowp is not None or task_of is not None
    worst, n, distinct = 0.0, 0, 0
    for r in reqs:
        prompt = prompts[r.rid]
        served = np.asarray(r.tokens, np.int64)
        seq = np.concatenate([prompt, served[:-1]])
        toks = np.zeros(length, np.int32)
        toks[:len(seq)] = seq
        rows = np.zeros(rows_len, np.int32)
        rows[:len(served)] = len(prompt) - 1 + np.arange(len(served))
        at = (jnp.asarray(toks), jnp.asarray(rows))
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(ref.served_logits(
                arch, root, *at, task_scales(ref, arch, root, seed, r.task)))
            if stand_in:
                other = task_of(r) if task_of is not None else r.task
                lo = np.asarray(ref.served_logits(
                    arch, root, *at,
                    task_scales(ref, arch, root, seed, other), lowp))
        lg = lg[:len(served)].astype(np.float64)
        pick = lo[:len(served)].argmax(-1) if stand_in else served
        gap = lg.max(-1) - lg[np.arange(len(served)), pick]
        worst = max(worst, float(gap.max()))
        n += len(served)
        distinct += len(set(served.tolist()))
    return {"gap": worst, "tokens": n, "requests": len(reqs),
            "distinct_tokens": distinct}


def leaf_gaps(prog: dict, ref: dict, keep=None) -> float:
    """Worst leaf's |‖prog‖ − ‖ref‖| over max(‖ref leaf‖, median ‖ref
    leaf‖), over the leaves in ``keep`` (all when None)."""
    rn = {k: float(np.linalg.norm(np.asarray(v, np.float64)))
          for k, v in ref.items()}
    med = float(np.median(list(rn.values())))
    worst = 0.0
    for k in ref:
        if keep is not None and k not in keep:
            continue
        pn = float(np.linalg.norm(np.asarray(prog[k], np.float64)))
        worst = max(worst, abs(pn - rn[k]) / max(rn[k], med, 1e-30))
    return worst


def batch_loss_and_grad(ref, arch, root, scales, rows, lowp=None):
    """Token-mean loss of a (B, S + 1) batch and its gradient in the
    scales, one row per reference call."""
    n = rows.shape[0] * (rows.shape[1] - 1)
    loss, grads = 0.0, None
    for row in rows:
        with jax.default_matmul_precision("highest"):
            l, g = ref.row_loss_and_grad(arch, root, scales,
                                         jnp.asarray(row[:-1]),
                                         jnp.asarray(row[1:]), lowp)
        loss += float(l) / n
        g = {k: np.asarray(v, np.float64) / n for k, v in g.items()}
        grads = g if grads is None else {k: grads[k] + g[k] for k in g}
    return loss, grads


def ref_readings(ref, arch, seed: int, rows: np.ndarray, opt: dict,
                 lowp=None) -> dict:
    """The reference's own AdamW steps on ``rows`` ((steps, B, S + 1)
    tokens): each step's loss, the first clipped gradient and the change
    over all steps, by linear name."""
    root = weights.root_key(seed)
    scales = ref.base_scales(arch, root)
    params = {k: np.asarray(v, np.float64) for k, v in scales.items()}
    p0 = dict(params)
    state = {"t": 0, "m": {k: 0.0 for k in params},
             "v": {k: 0.0 for k in params}}
    losses, grad1 = [], None
    for i in range(rows.shape[0]):
        loss, grads = batch_loss_and_grad(ref, arch, root, scales, rows[i],
                                          lowp)
        params, state, clip = ref.adamw(grads, state, params, opt)
        scales = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        losses.append(float(loss))
        if i == 0:
            grad1 = {k: g * clip for k, g in grads.items()}
    return {"losses": losses, "grad1": grad1,
            "change": {k: params[k] - p0[k] for k in params}}


def by_linear(prog: dict) -> dict:
    """The program's readings re-keyed from leaf paths
    (``layers/<linear>/scale``) to linear names."""
    name = lambda path: path[len("layers/"):-len("/scale")]
    return {"losses": prog["losses"],
            "grad1": {name(p): v for p, v in prog["grad1"].items()},
            "change": {name(p): v for p, v in prog["change"].items()}}


def train_numbers(got: dict, want: dict) -> dict:
    """Loss, first-gradient and change numbers of readings ``got`` against
    the float32 reference's ``want``."""
    gn = {k: float(np.linalg.norm(g)) for k, g in want["grad1"].items()}
    med = float(np.median(list(gn.values())))
    moving = {k for k, v in gn.items() if v >= 1e-3 * med}
    return {
        "loss": max(abs(a - b) / abs(b)
                    for a, b in zip(got["losses"], want["losses"])),
        "grad": leaf_gaps(got["grad1"], want["grad1"]),
        "change": leaf_gaps(got["change"], want["change"], keep=moving),
    }
