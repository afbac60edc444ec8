"""Projector construction & application for low-rank optimization.

A *projector* for a weight of shape ``(m, n)`` is an orthonormal matrix
``P`` of shape ``(d, r)`` where ``d = min(m, n)`` side:

  * ``side='left'``  (m <= n): R = P^T G   (r x n);  back: P @ D
  * ``side='right'`` (m >  n): R = G  P    (m x r);  back: D @ P^T

Selection methods (the paper's contribution + every baseline it compares to):

  * ``dominant``   -- GaLore/Q-GaLore: top-r left singular vectors.
  * ``sara``       -- the paper: importance-sample r of the singular vectors
                      with prob ∝ singular value (Gumbel top-k), sorted.
  * ``golore``     -- GoLore: rank-r random orthonormal basis (QR of Gaussian),
                      gradient-independent.
  * ``grass``      -- Grass-style structured sparsity: sample r *rows* with
                      prob ∝ squared row norm; P = selection columns (exactly
                      orthonormal).  Projection becomes a gather.
  * ``online_pca`` -- online subspace descent [LLCql24]: power-iteration-style
                      incremental update  P <- qr(P + eta * (G G^T) P).
  * ``identity``   -- r == d, P = I.  Testing: makes low-rank Adam coincide
                      exactly with full Adam.

All constructors take leading batch dims (scanned layers / experts) and vmap
internally.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import sampling as sampling_lib
from repro.core import svd as svd_lib
from repro.kernels.power_iter import ops as power_ops

METHODS = (
    "dominant",
    "sara",
    "golore",
    "grass",
    "online_pca",
    "identity",
)

# Methods whose refresh is SVD-free and therefore always batchable.
_SVD_FREE_METHODS = frozenset({"identity", "golore", "grass", "online_pca"})

# Methods whose refresh consumes PRNG entropy.  These are the methods
# rollback-and-resample (train/recovery.py) works for: folding the recovery
# attempt into the state key makes the next refresh draw a genuinely
# different subspace (sara re-runs its Gumbel top-k, golore draws a new
# random basis, grass re-samples rows).  ``dominant`` is deterministic
# top-k of the singular spectrum and ``identity`` is fixed -- the key never
# enters their refresh, so after a rollback they re-select the *same*
# subspace; ``online_pca``'s incremental update is likewise a deterministic
# function of (P_prev, G).  That determinism is the frozen-subspace failure
# mode the paper targets, restated as a recovery limitation.
STOCHASTIC_REFRESH_METHODS = frozenset({"sara", "golore", "grass"})


def refresh_is_stochastic(method: str) -> bool:
    """Does a new RNG key move this method's refreshed subspace?"""
    return method in STOCHASTIC_REFRESH_METHODS


def batched_refresh_supported(cfg: "ProjectorConfig") -> bool:
    """Can ``refresh_projector_stacked`` cover this config?

    The batched-refresh coverage matrix (DESIGN.md §2.6): SVD-free methods
    always batch; ``dominant``/``sara`` batch only on the ``randomized``
    backend (one stacked subspace-iteration chain per bucket).  The
    ``exact`` backend stays on the per-leaf loop -- paper-faithful runs
    (full ``k = d`` spectra through LAPACK) are untouched.
    """
    if cfg.method in _SVD_FREE_METHODS:
        return True
    if cfg.method in ("dominant", "sara"):
        return cfg.svd_backend == "randomized"
    return False


class ProjectorConfig(NamedTuple):
    method: str = "sara"
    rank: int = 128
    svd_backend: str = "exact"  # 'exact' | 'randomized'
    svd_oversample: int = 8
    svd_power_iters: int = 2
    # SARA with randomized SVD samples from a top-(pool) candidate set.
    sara_pool_factor: int = 4
    online_pca_lr: float = 0.1
    dtype: jnp.dtype = jnp.float32


def projection_side(shape) -> str:
    """Which side to project: the smaller of the two trailing dims."""
    m, n = shape[-2], shape[-1]
    return "left" if m <= n else "right"


def projector_dim(shape) -> int:
    return min(shape[-2], shape[-1])


def _t(x: jax.Array) -> jax.Array:
    return jnp.swapaxes(x, -1, -2)


# side='right' products are computed as the transpose of the side='left'
# product of the transposed operands -- the operand order of the bucketed
# engine's canonical (transposed) stacks.  XLA does not promise that
# A B and (B^T A^T)^T round alike (CPU dot kernels differ by an ulp between
# the two), so this is what keeps the per-leaf reference and the bucketed
# engine bit-for-bit on every backend.


def project(g: jax.Array, p: jax.Array, side: str) -> jax.Array:
    """R = P^T G (left) or G P (right); batched over leading dims."""
    if side == "left":
        return jnp.einsum("...dr,...dn->...rn", p, g)
    return _t(jnp.einsum("...dr,...dn->...rn", p, _t(g)))


def backproject(d: jax.Array, p: jax.Array, side: str) -> jax.Array:
    """Full-space update from projected direction."""
    if side == "left":
        return jnp.einsum("...dr,...rn->...dn", p, d)
    return _t(jnp.einsum("...dr,...rn->...dn", p, _t(d)))


def residual(g: jax.Array, p: jax.Array, side: str) -> jax.Array:
    """(I - P P^T) G  (left) / G (I - P P^T) (right): Fira's error term."""
    return g - backproject(project(g, p, side), p, side)


def _oriented(g: jax.Array, side: str) -> jax.Array:
    """Return gradient with the projected dim first: (d, other)."""
    return g if side == "left" else jnp.swapaxes(g, -1, -2)


def _refresh_single(
    g2: jax.Array,
    key: jax.Array,
    prev_p: Optional[jax.Array],
    cfg: ProjectorConfig,
    rank: int,
) -> jax.Array:
    """Build a (d, rank) projector from an oriented 2-D gradient (d, n')."""
    d = g2.shape[-2]
    method = cfg.method
    if method == "identity":
        return jnp.eye(d, rank, dtype=cfg.dtype)
    if method == "golore":
        z = jax.random.normal(key, (d, rank), dtype=jnp.float32)
        q, _ = jnp.linalg.qr(z)
        return q.astype(cfg.dtype)
    if method == "grass":
        row_energy = jnp.sum(g2.astype(jnp.float32) ** 2, axis=-1)  # (d,)
        idx = sampling_lib.gumbel_topk_indices(row_energy, rank, key)
        return jax.nn.one_hot(idx, d, dtype=cfg.dtype).T  # (d, r) selection
    if method == "online_pca":
        if prev_p is None:
            z = jax.random.normal(key, (d, rank), dtype=jnp.float32)
            q, _ = jnp.linalg.qr(z)
            return q.astype(cfg.dtype)
        g32 = g2.astype(jnp.float32)
        p32 = prev_p.astype(jnp.float32)
        # One step of subspace descent on ||G - P P^T G||_F^2, then
        # retraction.  (G G^T) P is the fused power-iteration primitive.
        step = cfg.online_pca_lr / (jnp.linalg.norm(g32) ** 2 + 1e-12)
        y = p32 + step * power_ops.power_iter_step(g32, p32)
        q, _ = jnp.linalg.qr(y)
        return q.astype(cfg.dtype)
    # SVD-based methods: dominant (GaLore) & sara.
    if method == "dominant":
        k = rank
    elif method == "sara":
        if cfg.svd_backend == "exact":
            k = d  # the paper samples from all d singular vectors
        else:
            k = min(d, cfg.sara_pool_factor * rank)
    else:
        raise ValueError(f"unknown projector method {method!r}")
    key_svd, key_sample = jax.random.split(key)
    u, s = svd_lib.topk_svd(
        g2,
        k,
        key_svd,
        backend=cfg.svd_backend,
        oversample=cfg.svd_oversample,
        power_iters=cfg.svd_power_iters,
    )
    if method == "dominant":
        return u.astype(cfg.dtype)
    p, _ = sampling_lib.sara_select(u, s, rank, key_sample)
    return p.astype(cfg.dtype)


def refresh_projector_stacked(
    g: jax.Array,
    keys: jax.Array,
    prev_p: Optional[jax.Array],
    cfg: ProjectorConfig,
    *,
    rank: int,
) -> jax.Array:
    """Refresh a whole (B, d, n) *oriented* gradient stack in one chain.

    The bucket-native refresh engine (core/buckets.bucketed_refresh) calls
    this once per bucket with every same-group leaf's slices stacked --
    batched Gaussian sketch, fused power iterations, batched thin QR, one
    small batched Gram ``eigh``, batched Gumbel-top-k -- instead of a
    per-leaf chain each.  ``keys`` is the (B,) per-slice key stack the
    caller derived with the per-leaf schedule (fold the global leaf index,
    split over leading dims), so every slice is bit-identical to what
    ``refresh_projector`` would produce for its leaf; only the dispatch
    shape changes.  ``prev_p`` is the (B, d, r) slice stack of the outgoing
    projectors (``online_pca`` consumes it; SVD methods ignore it).
    Coverage is decided by ``batched_refresh_supported`` -- callers must
    gate on it.

    Returns a (B, d, rank) stack with orthonormal columns per slice.
    """
    bsz, d, _ = g.shape
    rank = min(rank, d)
    method = cfg.method
    if method == "identity":
        eye = jnp.eye(d, rank, dtype=cfg.dtype)
        return jnp.broadcast_to(eye, (bsz, d, rank))
    if method == "golore":
        z = jax.vmap(
            lambda kk: jax.random.normal(kk, (d, rank), dtype=jnp.float32)
        )(keys)
        q, _ = jnp.linalg.qr(z)
        return q.astype(cfg.dtype)
    if method == "grass":
        row_energy = jnp.sum(g.astype(jnp.float32) ** 2, axis=-1)  # (B, d)
        idx = sampling_lib.gumbel_topk_indices_batched(row_energy, rank, keys)
        sel = jax.nn.one_hot(idx, d, dtype=cfg.dtype)  # (B, rank, d)
        return jnp.swapaxes(sel, -1, -2)
    if method == "online_pca":
        if prev_p is None:
            z = jax.vmap(
                lambda kk: jax.random.normal(kk, (d, rank), dtype=jnp.float32)
            )(keys)
            q, _ = jnp.linalg.qr(z)
            return q.astype(cfg.dtype)
        g32 = g.astype(jnp.float32)
        p32 = prev_p.astype(jnp.float32)
        norms = jax.vmap(jnp.linalg.norm)(g32)  # per-slice Frobenius
        step = (cfg.online_pca_lr / (norms**2 + 1e-12))[:, None, None]
        y = p32 + step * power_ops.power_iter_step(g32, p32)
        q, _ = jnp.linalg.qr(y)
        return q.astype(cfg.dtype)
    if method not in ("dominant", "sara"):
        raise ValueError(f"unknown projector method {method!r}")
    if cfg.svd_backend != "randomized":
        # the coverage matrix (DESIGN.md §2.6): exact stays per-leaf, and
        # callers gate on batched_refresh_supported before getting here.
        raise ValueError(
            f"stacked {method!r} refresh requires svd_backend='randomized'"
        )
    k = rank if method == "dominant" else min(d, cfg.sara_pool_factor * rank)
    split = jax.vmap(jax.random.split)(keys)
    key_svd, key_sample = split[:, 0], split[:, 1]
    u, s = svd_lib.randomized_svd_stacked(
        g, k, key_svd,
        oversample=cfg.svd_oversample, power_iters=cfg.svd_power_iters,
    )
    if method == "dominant":
        return u.astype(cfg.dtype)
    p, _ = sampling_lib.sara_select_batched(u, s, rank, key_sample)
    return p.astype(cfg.dtype)


def refresh_projector(
    g: jax.Array,
    key: jax.Array,
    prev_p: Optional[jax.Array],
    cfg: ProjectorConfig,
    *,
    side: Optional[str] = None,
    rank: Optional[int] = None,
) -> jax.Array:
    """Construct a new projector from gradient ``g`` (any leading batch dims).

    Returns P of shape (*batch, d, rank), orthonormal columns per batch slice.
    """
    side = side or projection_side(g.shape)
    d = projector_dim(g.shape)
    rank = min(rank or cfg.rank, d)
    g2 = _oriented(g, side)
    batch_shape = g2.shape[:-2]
    if not batch_shape:
        return _refresh_single(g2, key, prev_p, cfg, rank)
    nb = 1
    for b in batch_shape:
        nb *= b
    gf = g2.reshape((nb,) + g2.shape[-2:])
    pf = None
    if prev_p is not None:
        pf = prev_p.reshape((nb,) + prev_p.shape[-2:])
    keys = jax.random.split(key, nb)
    fn = functools.partial(_refresh_single, cfg=cfg, rank=rank)
    if pf is None:
        out = jax.vmap(lambda gg, kk: fn(gg, kk, None))(gf, keys)
    else:
        out = jax.vmap(fn)(gf, keys, pf)
    return out.reshape(batch_shape + out.shape[-2:])
