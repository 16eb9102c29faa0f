"""The comparison that decides ``correct``.

After the window, a sample of the requests the engine finished (and,
of a backlog cut at the close, of those it was serving) is run through
the float32 reference: each prompt with the tokens the engine
served after it, in one causal pass.  At every position that produced
a served token, the gap is how far that token's reference logit lies
below the reference's best logit there.  The number compared is the
widest gap over the sample.  Greedy decoding in a precision near the
reference's serves the reference's best token or a near-tie, so the
gap stays near the logits' rounding; a fault or a lower precision
serves tokens the reference ranks well below its best.

The sample is drawn from the seed: the request with the longest prompt
plus answer, the one that served most tokens, the one that took most
of its prompt from the prefix cache, then others at random until
``MIN_TOKENS`` served tokens and ``MIN_REQUESTS`` requests are covered
(at most ``MAX_REQUESTS``).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

MIN_TOKENS = 400
MIN_REQUESTS = 4
MAX_REQUESTS = 12


def sample(done: Sequence[Tuple], seed: int, cached: Sequence[int] = ()
           ) -> List[int]:
    """Indices into ``done`` ((spec, request) pairs) to compare."""
    n = len(done)
    if not n:
        return []
    served = [len(r.out) for _, r in done]
    total = [len(s.prompt) + k for (s, _), k in zip(done, served)]
    pick = [int(np.argmax(total)), int(np.argmax(served))]
    if cached and max(cached) > 0:
        pick.append(int(np.argmax(cached)))
    rng = np.random.default_rng([seed, 0x636865636B])     # "check"
    for i in rng.permutation(n):
        k = len(set(pick))
        if k >= MAX_REQUESTS or (k >= MIN_REQUESTS and sum(
                served[j] for j in set(pick)) >= MIN_TOKENS):
            break
        pick.append(int(i))
    return sorted(set(pick))


def gap_fn(config: Dict, ref, fp8: bool = False):
    """Jitted ``(weights, tokens, at, want) -> (gaps, control_gaps)``:
    the reference gap of each wanted token and, with ``fp8``, the
    reference gap of the token the fp8 reference ranks first."""
    import jax
    import jax.numpy as jnp

    def fn(w, tokens, at, want):
        logits = ref.logits_at(config, w, tokens, at)
        best = jnp.max(logits, axis=-1)
        gaps = best - jnp.take_along_axis(logits, want[:, None], -1)[:, 0]
        if not fp8:
            return gaps, gaps
        low = ref.logits_at(config, w, tokens, at, fp8=True)
        pick = jnp.argmax(low, axis=-1)
        return gaps, best - jnp.take_along_axis(logits, pick[:, None],
                                                 -1)[:, 0]

    return jax.jit(fn)


def compare(config: Dict, ref, key, done: Sequence[Tuple], seed: int, *,
            max_len: int, max_new: int, fp8: bool = False,
            cached: Sequence[int] = ()) -> Dict:
    """Run the reference over the sampled requests.  ``done`` holds
    (spec, request) pairs of finished requests; the weights are made
    again from ``key``, as the program's were."""
    import jax
    import jax.numpy as jnp

    picks = sample(done, seed, cached)
    out = {"max_logit_gap": float("inf"), "control_gap": None,
           "compared_tokens": 0, "compared_requests": len(picks)}
    if not picks:
        return out
    w = jax.jit(lambda k: ref.make_weights(config, k))(key)
    fn = gap_fn(config, ref, fp8)
    worst, worst_ctrl, tokens = 0.0, 0.0, 0
    for i in picks:
        spec, r = done[i]
        prompt, served = list(spec.prompt), list(r.out)
        seq = prompt + served[:-1]
        toks = np.zeros((max_len,), np.int32)
        toks[:len(seq)] = seq
        n = len(served)
        at = np.full((max_new,), len(prompt) - 1 + n - 1, np.int32)
        at[:n] = len(prompt) - 1 + np.arange(n)
        want = np.zeros((max_new,), np.int32)
        want[:n] = served
        gaps, ctrl = fn(w, jnp.asarray(toks), jnp.asarray(at),
                        jnp.asarray(want))
        gaps, ctrl = np.asarray(gaps)[:n], np.asarray(ctrl)[:n]
        worst = max(worst, float(gaps.max()))
        worst_ctrl = max(worst_ctrl, float(ctrl.max()))
        tokens += n
    out.update(max_logit_gap=worst, compared_tokens=tokens,
               control_gap=worst_ctrl if fp8 else None)
    return out
