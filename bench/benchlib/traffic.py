"""The one generator of the benchmark's inputs, driven by a traffic file
(``bench/traffic/<name>.json``) and the run's seed.

Every seed gets the same multiset of sizes and arrival gaps, in another
order: sizes and gaps are the distributions' quantiles at (i + 0.5) / n,
shuffled by the seed. So two seeds do the same work, and the spread
between runs is the system's, not the draw's. Token ids are drawn from a
Zipf law over the vocabulary (rank order shuffled by the seed).

Adapted from the program's ``launch/serve.make_trace`` (Poisson arrivals,
mixed lengths) and ``data/synthetic`` (seeded token batches); the copies
here are the yardstick and do not follow later changes to those.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _norm_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse standard normal CDF (Acklam's rational approximation,
    relative error < 1.2e-9)."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = ((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q
                           + 1))
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                 + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q
                            + 1))
    q = p[mid] - 0.5
    r = q * q
    out[mid] = ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
                 + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3])
                                 * r + b[4]) * r + 1))
    return out


def lognormal_quantiles(n: int, median: float, sigma: float) -> np.ndarray:
    return median * np.exp(sigma * _norm_ppf(_quantiles(n)))


def snap(values: np.ndarray, buckets: Sequence[int]) -> np.ndarray:
    """Each value to the bucket nearest in log scale."""
    b = np.asarray(sorted(buckets), np.float64)
    idx = np.argmin(np.abs(np.log(values)[:, None] - np.log(b)[None]), axis=1)
    return b[idx].astype(np.int64)


def zipf_tokens(rng: np.random.Generator, n: int, vocab: int,
                s: float) -> np.ndarray:
    """``n`` token ids whose rank frequencies follow r^-s; which id holds
    which rank is shuffled by ``rng``."""
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -s
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    ranks = np.minimum(np.searchsorted(cdf, rng.random(n)), vocab - 1)
    return rng.permutation(vocab)[ranks].astype(np.int32)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray
    max_new: int
    due: float = 0.0   # seconds after the traffic's start


def closed_set(t: dict, vocab: int, max_len: int, seed: int) -> List[Request]:
    """A fixed set of sessions, all due at 0: contexts cycle through
    ``t["contexts"]`` (then shuffled) and each decodes until its context
    reaches ``max_len`` - 1."""
    n = int(t["sessions"])
    rng = rng_for(seed, 1)
    ctx = np.asarray([t["contexts"][i % len(t["contexts"])]
                      for i in range(n)])[rng.permutation(n)]
    return [Request(uid=i, prompt=zipf_tokens(rng, int(c), vocab,
                                              t["zipf_s"]),
                    max_new=int(max_len - 1 - c))
            for i, c in enumerate(ctx)]


def open_loop(t: dict, vocab: int, seconds: float, seed: int
              ) -> List[Request]:
    """Poisson arrivals at ``t["rate"]`` per second over ``seconds``:
    exponential gaps at fixed quantiles, shuffled. Prompt lengths are
    lognormal snapped to ``t["prompt_buckets"]``; ``max_new`` is lognormal
    clipped to [out_min, out_max]."""
    n = max(1, int(round(t["rate"] * seconds)))
    rng = rng_for(seed, 2)
    gaps = -np.log(1 - _quantiles(n)) / t["rate"]
    due = np.cumsum(gaps[rng.permutation(n)]) - gaps.min() / 2
    prompt = snap(lognormal_quantiles(n, t["prompt_median"],
                                      t["prompt_sigma"]),
                  t["prompt_buckets"])[rng.permutation(n)]
    out = np.clip(np.rint(lognormal_quantiles(n, t["out_median"],
                                              t["out_sigma"])),
                  t["out_min"], t["out_max"]).astype(np.int64)
    out = out[rng.permutation(n)]
    return [Request(uid=i, prompt=zipf_tokens(rng, int(p), vocab,
                                              t["zipf_s"]),
                    max_new=int(m), due=float(d))
            for i, (p, m, d) in enumerate(zip(prompt, out, due))]


def train_batch_fn(t: dict, batch: int, seq: int, vocab: int):
    """Jitted ``(key, step) -> {"tokens", "labels"}``, made on the device.

    Each row is ``seq + 1`` Zipf-distributed ids (inverse CDF), shifted by
    one for the labels. Rows take the Zipf exponents evenly spaced over
    ``t["zipf_s_range"]``, in an order drawn per step, so rows differ in
    entropy (and in loss) while every batch does the same work."""
    import jax
    import jax.numpy as jnp

    lo, hi = t["zipf_s_range"]
    spaced = jnp.asarray(lo + (hi - lo) * _quantiles(batch), jnp.float32)
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)

    def make(key, step):
        k_step = jax.random.fold_in(jax.random.fold_in(key, 1), step)
        k_order, k_u = jax.random.split(k_step)
        perm = jax.random.permutation(jax.random.fold_in(key, 2), vocab)
        exps = spaced[jax.random.permutation(k_order, batch)]
        cdf = jnp.cumsum(ranks[None, :] ** -exps[:, None], axis=1)
        cdf = cdf / cdf[:, -1:]
        u = jax.random.uniform(k_u, (batch, seq + 1), jnp.float32)
        idx = jax.vmap(jnp.searchsorted)(cdf, u)
        toks = perm[jnp.minimum(idx, vocab - 1)].astype(jnp.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    return jax.jit(make)


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in [0, 100]); None when empty."""
    v = sorted(values)
    if not v:
        return None
    k = max(0, min(len(v) - 1, math.ceil(q / 100 * len(v)) - 1))
    return float(v[k])
