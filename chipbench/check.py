"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the engine's state is freed, a sample of the
requests it finished (drawn from the seed, the longest always in it) is run
through the family's plain float32 reference: one forward pass over each
prompt with its served tokens. For every served token the gap is how far its
reference logit lies below the reference's best at that position. A sound
greedy engine in bfloat16 picks tokens within rounding of the best, so the gaps
are small; a token from a wrong cache row, a wrong rotary offset, a missed
chunk or a wrong expert lies far below it. The widest gap, the mean and the
95th percentile are read; a cell's file holds to a limit those that separate
a sound run from the control at its size (PERF.md, section 4).

Where a model routes its tokens to experts, a position at which the reference's
own router is all but tied between the last expert it takes and the first it
leaves says nothing: rounding alone decides which a sound program takes, and
the token it then serves can lie far from the reference's. Such positions are
left out by a rule on the reference's routing margin (the cell's
``router_margin_min``), never by what the program did; the count left out is
printed.

The control (never run by a benchmark run) puts the reference computed in int8
in the program's place: at every compared position, the gap of the token the
lower precision puts first.
"""

from __future__ import annotations

import numpy as np

from . import traffic


def pick_sample(records, seed: int, n: int):
    """n finished requests: the longest, then others drawn from the seed."""
    done = [r for r in records if r.status == "ok" and r.tokens is not None]
    if not done:
        return []
    done.sort(key=lambda r: (r.phase, r.index))
    longest = max(done, key=lambda r: (r.prompt_len + r.new_tokens, -r.index))
    rest = [r for r in done if r is not longest]
    order = traffic.rng_for(seed, "sample").permutation(len(rest))
    return [longest] + [rest[i] for i in order[: max(0, n - 1)]]


def row_faults(records, vocab_size: int) -> int:
    """Finished rows that are not prompt + budget tokens of the vocabulary."""
    bad = 0
    for r in records:
        if r.status != "ok":
            continue
        row = np.asarray(r.tokens)
        bad += int(r.new_tokens != r.budget or row.shape != (r.prompt_len + r.budget,)
                   or row.min() < 0 or row.max() >= vocab_size)
    return bad


def build_gap_fn(family, cfg: dict, control: str | None):
    """jit of ids (T,) -> (gaps (T,), routing margins (T,), control gaps (T,)
    or None); position t is judged on the token at t + 1."""
    import jax
    import jax.numpy as jnp

    def gaps(weights, ids):
        logits, margin = family.reference_logits(cfg, weights, ids, with_margin=True)
        best = jnp.max(logits, axis=-1)
        nxt = jnp.roll(ids, -1)
        gap = best - jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
        if control is None:
            return gap, margin, None
        low = jnp.argmax(family.reference_logits(cfg, weights, ids, control), axis=-1)
        return gap, margin, best - jnp.take_along_axis(logits, low[:, None], axis=-1)[:, 0]

    return jax.jit(gaps)


def gap_numbers(gaps, margins, margin_min: float, prefix: str = "") -> dict:
    """The widest gap, the mean and the 95th percentile over the positions
    whose routing the reference decides by ``margin_min`` or more. A cell's
    file says which of them it holds to a limit."""
    keep = margins >= margin_min
    if not keep.any():
        return {}
    return {prefix + "gap_max": float(gaps[keep].max()),
            prefix + "gap_mean": float(gaps[keep].mean()),
            prefix + "gap_p95": float(np.quantile(gaps[keep], 0.95))}


def served_gaps(family, cfg: dict, weights: dict, sample, pad_to: int, margin_min: float,
                control: str | None = None, say=None, dump: str | None = None) -> dict:
    """Gaps of every served token of the sample. Sequences are padded to one
    length (causal: the padding changes nothing before it), so one program
    serves every request. With a control, ``say`` is told the numbers under
    other margins too, and ``dump`` (an ``.npz`` path) gets every token's gap,
    margin and control gap: what the cell's ``router_margin_min`` and limits
    were chosen from."""
    fn = build_gap_fn(family, cfg, control)
    gaps, margins, control_gaps = [], [], []
    for r in sample:
        row = np.asarray(r.tokens, np.int32)[: r.prompt_len + r.new_tokens]
        ids = np.zeros((pad_to,), np.int32)
        ids[: row.size] = row
        g, m, c = fn(weights, ids)
        served = slice(r.prompt_len - 1, r.prompt_len + r.new_tokens - 1)
        gaps.append(np.asarray(g)[served])
        margins.append(np.asarray(m)[served])
        if c is not None:
            control_gaps.append(np.asarray(c)[served])
    out = {"requests": len(sample), "tokens": int(sum(g.size for g in gaps))}
    if not gaps:
        return out
    allg, allm = np.concatenate(gaps), np.concatenate(margins)
    out["tokens_left_out"] = int((allm < margin_min).sum())
    out.update(gap_numbers(allg, allm, margin_min))
    if control_gaps:
        allc = np.concatenate(control_gaps)
        out.update(gap_numbers(allc, allm, margin_min, "control_"))
        if dump is not None:
            np.savez(dump, gap=allg, margin=allm, control_gap=allc,
                     request=np.concatenate([np.full(g.size, i) for i, g in enumerate(gaps)]))
        for eps in (0.0, 0.002, 0.005, 0.01, 0.02, 0.05, 0.1):
            if say is not None and np.isfinite(allm).any():
                say(f"margin >= {eps}: {int((allm >= eps).sum())} of {allm.size} tokens; "
                    f"program {gap_numbers(allg, allm, eps)}; "
                    f"control {gap_numbers(allc, allm, eps, 'control_')}")
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, list]:
    """Each number beside its limit; correct when none is over. A number the
    run could not produce is over."""
    compared = []
    for name, limit in limits.items():
        value = numbers.get(name)
        compared.append({"name": name, "value": value, "limit": limit,
                         "ok": value is not None and np.isfinite(value) and value <= limit})
    return all(c["ok"] for c in compared), compared
