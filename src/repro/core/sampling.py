"""SARA's importance sampling (Algorithm 2, lines 4-5), JAX-native.

The paper samples ``r`` of ``m`` singular vectors *without replacement* with
per-draw probability proportional to the singular values:

    P{(I_1..I_r) = (i_1..i_r)} = prod_k  w_{i_k} / (1 - w_{i_1} - .. - w_{i_{k-1}})

with w_i = S_i / sum_j S_j.  The torch implementation does this on host with
``numpy.random.choice(..., replace=False)``; here we use the **Gumbel top-k
trick** (Efraimidis-Spirakis / Kool et al.), which realizes *exactly* this
sequential sampling law fully inside ``jit``:

    keys_i = log w_i + Gumbel_i ;  I = top-r(keys)

Taking the top-r of Gumbel-perturbed log-weights is distributionally identical
to sequential weighted sampling without replacement, is O(m log m), traceable,
vmappable over layer/expert stacks, and needs no host callback.

Indices are then sorted ascending (Alg. 2 line 5) so the selected basis columns
keep a stable ordering across refreshes and optimizer-state rows stay aligned.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def gumbel_topk_indices(
    weights: jax.Array,
    r: int,
    key: jax.Array,
    *,
    sort_indices: bool = True,
) -> jax.Array:
    """Sample ``r`` distinct indices with prob proportional to ``weights``.

    ``weights``: (m,) nonnegative.  Zero-weight entries are never selected
    (matching the sequential law: w_i = 0 => never drawn) unless fewer than
    ``r`` positive weights exist, in which case the remaining slots fall back
    to uniform among the zero-weight entries (degenerate case; keeps the
    projector well-defined on e.g. a zero gradient at step 0).
    """
    m = weights.shape[-1]
    if r > m:
        raise ValueError(f"cannot sample {r} of {m} indices without replacement")
    w = jnp.asarray(weights, jnp.float32)
    total = jnp.sum(w)
    # Degenerate fallback: if the weight vector is (numerically) all-zero,
    # sample uniformly.  This happens for an exactly-zero gradient.
    w = jnp.where(total > 0, w, jnp.ones_like(w))
    logw = jnp.where(w > 0, jnp.log(jnp.maximum(w, 1e-38)), _NEG_INF)
    gumbel = jax.random.gumbel(key, (m,), dtype=jnp.float32)
    scores = logw + gumbel
    _, idx = jax.lax.top_k(scores, r)
    if sort_indices:
        idx = jnp.sort(idx)
    return idx


def sara_select(
    u: jax.Array,
    s: jax.Array,
    r: int,
    key: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """SARA subspace selection: sample r columns of ``u`` with prob ∝ ``s``.

    ``u``: (d, k) left singular vectors, ``s``: (k,) singular values.
    Returns (P (d, r), idx (r,)).  ``k`` may be < d when a truncated
    (randomized) SVD supplies only a top-k pool -- the sampling is then over
    that pool (documented deviation; ``exact`` backend gives k = d choices
    as in the paper).
    """
    with jax.named_scope("sara_sample"):
        idx = gumbel_topk_indices(s, r, key, sort_indices=True)
        p = jnp.take(u, idx, axis=-1)
    return p, idx


def gumbel_topk_indices_batched(
    weights: jax.Array,
    r: int,
    keys: jax.Array,
    *,
    sort_indices: bool = True,
) -> jax.Array:
    """``gumbel_topk_indices`` over a (B, m) weight stack with (B,) keys.

    One batched dispatch chain (batched Gumbel draw + batched top-k) whose
    slice ``b`` is bit-identical to ``gumbel_topk_indices(weights[b], r,
    keys[b])`` -- the bucketed refresh engine samples every leaf of a
    bucket's singular-value stack in one shot.  Returns (B, r) indices.
    """
    return jax.vmap(
        lambda w, k: gumbel_topk_indices(w, r, k, sort_indices=sort_indices)
    )(weights, keys)


def sara_select_batched(
    u: jax.Array,
    s: jax.Array,
    r: int,
    keys: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """``sara_select`` over stacked (B, d, k) bases / (B, k) spectra.

    Per-slice keys make slice ``b`` bit-identical to ``sara_select(u[b],
    s[b], r, keys[b])``; the whole stack costs one batched Gumbel top-k and
    one batched gather.  Returns (P (B, d, r), idx (B, r)).
    """
    return jax.vmap(lambda uu, ss, kk: sara_select(uu, ss, r, kk))(u, s, keys)


def inclusion_probabilities_mc(
    weights: jax.Array, r: int, key: jax.Array, n_samples: int = 4096
) -> jax.Array:
    """Monte-Carlo estimate of per-index inclusion probabilities.

    Test helper: estimates P[i in I] under the sampler, to be compared with a
    direct simulation of the paper's sequential law.  Vectorized over samples.
    """
    keys = jax.random.split(key, n_samples)
    idxs = jax.vmap(
        lambda k: gumbel_topk_indices(weights, r, k, sort_indices=False)
    )(keys)
    m = weights.shape[-1]
    onehot = jax.nn.one_hot(idxs, m, dtype=jnp.float32).sum(axis=1)  # (N, m)
    return onehot.mean(axis=0)


def sequential_sample_reference(weights, r, rng):
    """NumPy reference of the paper's sequential sampling law (test oracle)."""
    import numpy as np

    w = np.asarray(weights, dtype=np.float64).copy()
    idx = []
    for _ in range(r):
        p = w / w.sum()
        i = rng.choice(len(w), p=p)
        idx.append(int(i))
        w[i] = 0.0
    return sorted(idx)
