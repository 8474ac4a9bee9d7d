"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the finished requests is drawn
from the seed, the longest among them.  The plain reference
(``bench/reference/<family>.py``, float32 at HIGHEST precision, same
weights) runs once over each prompt with its served tokens, and for every
served token reads how far its logit lies below the reference's largest
at that position.  The number compared is the mean of those gaps over the
sample's served tokens; the widest gap is printed beside it.

The control is the reference computed with fp8 operands in every matmul,
one step below the configuration's bf16: at the same positions its first
choices take the served tokens' place, and their mean gap is judged by the
same limit.
"""
from __future__ import annotations

import numpy as np


def sample(finished, seed: int, *, min_tokens: int, min_requests: int):
    """The longest finished request, then others in a seeded order until
    the sample holds ``min_tokens`` served tokens and ``min_requests``
    requests."""
    if not finished:
        return []
    by_len = sorted(finished, key=lambda r: (-len(r.out_tokens), r.uid))
    rest = by_len[1:]
    rng = np.random.default_rng([int(seed), 0x5EED])
    picked = [by_len[0]]
    for i in rng.permutation(len(rest)):
        if (sum(len(r.out_tokens) for r in picked) >= min_tokens
                and len(picked) >= min_requests):
            break
        picked.append(rest[i])
    return picked


def _sequence(req, t_pad: int):
    """Tokens fed for ``req`` (prompt, then every served token but the
    last), padded to ``t_pad``, and the served token due at each position."""
    prompt = np.asarray(req.prompt, np.int32)
    out = np.asarray(req.out_tokens, np.int32)
    seq = np.concatenate([prompt, out[:-1]])
    if len(seq) > t_pad:
        raise ValueError(f"request {req.uid}: {len(seq)} tokens > {t_pad}")
    toks = np.zeros(t_pad, np.int32)
    toks[: len(seq)] = seq
    target = np.zeros(t_pad, np.int32)
    lo = len(prompt) - 1
    target[lo: lo + len(out)] = out
    return toks, target, lo, lo + len(out)


def served_gaps(ref, conf, weights, reqs, t_pad: int, *, control=False) -> dict:
    """Over the served tokens of ``reqs``: the mean gap of a served token
    below the reference's best logit (``mean_gap``, the number compared),
    the widest gap (``gap``), and the share of served tokens that are not
    the reference's first choice; with ``control``, the same three for the
    fp8 control's first choices at the same positions (``control_*``).
    ``n`` counts the tokens compared."""
    import jax.numpy as jnp

    gaps, ctrl = [], []
    for req in reqs:
        toks, target, lo, hi = _sequence(req, t_pad)
        targets = [target]
        if control:
            _, am8, _ = ref.scores(conf, weights, jnp.asarray(toks), len(req.prompt),
                                   jnp.asarray(target[None]), fp8=True)
            targets.append(np.asarray(am8))
        mx, _, at = ref.scores(conf, weights, jnp.asarray(toks), len(req.prompt),
                               jnp.asarray(np.stack(targets)))
        mx, at = np.asarray(mx)[lo:hi], np.asarray(at)[:, lo:hi]
        gaps.append(mx - at[0])
        if control:
            ctrl.append(mx - at[1])
    out = {"n": sum(len(g) for g in gaps)}
    for key, rows in (("", gaps), ("control_", ctrl)):
        if rows:
            g = np.concatenate(rows)
            out[f"{key}gap"] = float(g.max())
            out[f"{key}mean_gap"] = float(g.mean())
            out[f"{key}miss_share"] = float(np.mean(g > 0))
    return out
