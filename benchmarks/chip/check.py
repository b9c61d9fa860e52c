"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests it finished, drawn from the seed and always holding the
longest, is run through the plain float32 reference
(``reference/dense_lm.py``) over each prompt with its served tokens. The
number compared is the widest gap by which a served token's logit lies
below the reference's best logit at that position. The served tokens are
greedy, so a sound program reads only the rounding of its own precision.

Besides the gap, every finished request must have streamed exactly the
tokens it asked for (``short_streams``, limit 0).
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.reference import dense_lm
from benchmarks.chip.reference.dense_lm import Q_BLOCK
from benchmarks.chip.traffic import prompt_tokens


def sample(finished: list[tuple], seed: int, want_tokens: int,
           max_requests: int) -> list[tuple]:
    """``finished``: (rid, prompt_len, served_tokens, migrated). The
    longest request, one migrated request where any migrated, then others
    in a seeded order until ``want_tokens`` served tokens are covered."""
    if not finished:
        return []
    order = sorted(finished, key=lambda f: (-(f[1] + len(f[2])), f[0]))
    picked = [order[0]]
    rest = [order[i] for i in
            np.random.default_rng([seed, 7]).permutation(len(order))
            if i != 0]
    migrated = [f for f in rest if f[3]]
    if migrated and not picked[0][3]:
        picked.append(migrated[0])
        rest.remove(migrated[0])
    for f in rest:
        if sum(len(p[2]) for p in picked) >= want_tokens \
                or len(picked) >= max_requests:
            break
        picked.append(f)
    return picked


def bucket(n: int) -> int:
    b = Q_BLOCK
    while b < n:
        b *= 2
    return b


class Reference:
    """The reference's weights for one seed, made on the default device,
    and its jitted ``gaps`` (one program per sequence bucket)."""

    def __init__(self, cfg: dict, seed: int, dtype):
        import jax
        self.spec = dense_lm.spec_of(cfg)
        self.params = jax.jit(dense_lm.init_params, static_argnums=(1, 2))(
            jax.random.PRNGKey(seed), self.spec, dtype)
        self._gaps = jax.jit(dense_lm.gaps, static_argnums=(3, 4))

    def run(self, rid: int, prompt_len: int, served: list[int],
            low: bool = False, extra=None):
        """Per served position: (best logit, logit of each target row,
        argmax). Targets: the served tokens, then ``extra`` rows."""
        import jax.numpy as jnp
        prompt = prompt_tokens(rid, prompt_len, self.spec.vocab)
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        t = bucket(len(seq))
        tokens = np.zeros(t, np.int32)
        tokens[:len(seq)] = seq
        pos = np.arange(prompt_len - 1, prompt_len - 1 + len(served))
        rows = [np.asarray(served, np.int32)] + list(extra or [])
        targets = np.zeros((len(rows), t), np.int32)
        for i, r in enumerate(rows):
            targets[i, pos] = r
        best, picked, arg = self._gaps(self.params, jnp.asarray(tokens),
                                       jnp.asarray(targets), self.spec, low)
        return (np.asarray(best)[pos], np.asarray(picked)[:, pos],
                np.asarray(arg)[pos])


def widest_gap(ref: Reference, picked: list[tuple]) -> tuple[float, int]:
    """Widest (best - served) logit gap over the sampled requests, and
    how many served tokens it covered."""
    widest, n = 0.0, 0
    for rid, prompt_len, served, _ in picked:
        best, logit, _ = ref.run(rid, prompt_len, served)
        widest = max(widest, float(np.max(best - logit[0])))
        n += len(served)
    return widest, n


def control_gap(ref: Reference, picked: list[tuple]) -> float:
    """The control's reading: at each served position, the token that the
    reference computed in float8 puts first, and the gap of that token
    below the float32 reference's best. Widest over the sample."""
    widest = 0.0
    for rid, prompt_len, served, _ in picked:
        _, _, low_first = ref.run(rid, prompt_len, served, low=True)
        best, logit, _ = ref.run(rid, prompt_len, served, extra=[low_first])
        widest = max(widest, float(np.max(best - logit[1])))
    return widest
