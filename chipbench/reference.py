"""Plain reference of the cells' training step, written from the published
descriptions and imported from nothing of the program.

* The model: the configuration's family (``chipbench.families``) gives
  the forward and its mean next-token loss; here its gradients are taken,
  in float32 with every matrix product at ``Precision.HIGHEST`` (``mm``
  and ``ein``, which the families' forwards call).
* The optimizer: GaLore with SARA subspace selection (Algorithm 1 and 2 of
  the paper) around Adam.  A low-rank leaf is a stack of matrices (the
  family says which leaves, and how many leading axes stack them, taken
  row-major as one slice axis); each matrix is oriented so that its
  smaller side ``d`` is projected; a refresh takes a randomized SVD of the
  gradient (a Gaussian sketch of width ``k + oversample`` with
  ``k = pool x r``, subspace iterations with a QR between them, the small
  SVD from the eigendecomposition of B B^T in float64 on the host),
  samples ``r`` of the ``k`` left singular vectors without replacement
  with probability proportional to the singular values (the Gumbel top-k
  form of that law) and sorts them; the moments are kept as they are
  (GaLore's carry).  Every step: the gradients clipped to a global norm,
  then Adam on R = P^T G and W <- W - lr(t) alpha P N, lr(t) the paper's
  warmup-cosine schedule.  Every other leaf takes full-rank Adam.
* SARA's draw is seeded.  The keys follow the schedule the configuration
  states: the optimizer's key starts as ``PRNGKey(seed)`` and is split at
  each refresh; leaf ``i`` of the flattened tree folds ``i`` into the
  refresh key, and a stack of ``S`` matrices splits that over its slices;
  each slice splits its key into the sketch's and the sample's.

``prec`` rounds the inputs of every model matrix product to a lower
precision in the forward pass: "fp8" is the control of the correctness
check (the step below the configuration's bfloat16); "bf16" is the
configuration's own compute precision, a witness of how far rounding alone
moves the readings.
"""
from __future__ import annotations

import concurrent.futures
import functools
import math
from typing import Any, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import families
from chipbench import weights as weights_lib

HIGHEST = jax.lax.Precision.HIGHEST
# inputs of every model matrix product rounded to this type; whether the
# backward's cotangents are rounded too (float8's e4m3 has no range for
# unscaled cotangents, so its forward alone is rounded); whether the
# refresh's sketch products are rounded too (as the program's, at the
# TPU's default matmul precision)
ROUND = {"f32": (None, False, False), "bf16": (jnp.bfloat16, True, True),
         "fp8": (jnp.float8_e4m3fn, False, False)}
EIGH_THREADS = 8  # host threads for the slices' small eigendecompositions


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _cast(x, prec):
    return x.astype(ROUND[prec][0]).astype(jnp.float32)


def _cast_fwd(x, prec):
    return _cast(x, prec), None


def _cast_bwd(prec, _, g):
    return (_cast(g, prec) if ROUND[prec][1] else g,)


_cast.defvjp(_cast_fwd, _cast_bwd)


def _round(x, prec):
    """x rounded to ``prec`` in the forward pass; the cotangent passes
    back rounded to it as well where ``ROUND`` says so."""
    return x if ROUND[prec][0] is None else _cast(x, prec)


def mm(a, b, prec="f32"):
    return jnp.matmul(_round(a, prec), _round(b, prec), precision=HIGHEST)


def ein(spec, a, b, prec="f32"):
    return jnp.einsum(spec, _round(a, prec), _round(b, prec),
                      precision=HIGHEST)


# ---------------------------------------------------------------------------
# the optimizer
# ---------------------------------------------------------------------------


class OptState(NamedTuple):
    step: jax.Array
    key: jax.Array
    proj: Tuple  # per leaf: (S, d, r) projector, or None (full rank)
    m: Tuple  # per leaf: first moment ((S, r, n') oriented, or leaf-shaped)
    v: Tuple


def _slices(x):
    """A low-rank leaf as one stack of S matrices, (S, rows, cols)."""
    return x.reshape((-1,) + x.shape[-2:])


def _left(shape) -> bool:
    return shape[-2] <= shape[-1]


def _orient(x, left: bool):
    return x if left else jnp.swapaxes(x, -1, -2)


def opt_init(params, oc, seed: int, layout) -> OptState:
    if oc["momentum_carry"] != "keep":
        raise ValueError(f"momentum_carry {oc['momentum_carry']!r}: the "
                         "reference keeps the moments at a refresh")
    proj, m, v = [], [], []
    for leaf, x in zip(layout, jax.tree_util.tree_leaves(params)):
        if leaf.lowrank:
            left = _left(x.shape)
            d, n = (x.shape[-2], x.shape[-1]) if left else (
                x.shape[-1], x.shape[-2])
            r = min(oc["rank"], d)
            lead = (weights_lib.slices(x.shape),)
            proj.append(jnp.broadcast_to(jnp.eye(d, r, dtype=jnp.float32),
                                         lead + (d, r)))
            m.append(jnp.zeros(lead + (r, n), jnp.float32))
            v.append(jnp.zeros(lead + (r, n), jnp.float32))
        else:
            proj.append(None)
            m.append(jnp.zeros(x.shape, jnp.float32))
            v.append(jnp.zeros(x.shape, jnp.float32))
    return OptState(jnp.zeros((), jnp.int32), jax.random.PRNGKey(seed),
                    tuple(proj), tuple(m), tuple(v))


def sketch(g, key, r, oc, prec="f32"):
    """The device half of one slice's refresh: the range basis Q (d, kp)
    of the subspace-iterated Gaussian sketch of g (d, n), the Gram matrix
    of B = Q^T g, and the sample's key; ``prec`` rounds the inputs of its
    matrix products as in the model."""
    d, n = g.shape
    k = min(oc["sara_pool_factor"] * r, d)
    kp = min(k + oc["svd_oversample"], d)
    iters = oc["svd_power_iters"] if kp < d else 0
    k_svd, k_sample = jax.random.split(key)
    y = mm(g, jax.random.normal(k_svd, (n, kp), jnp.float32), prec)
    for _ in range(iters):
        q, _ = jnp.linalg.qr(y)
        y = mm(g, mm(g.T, q, prec), prec)
    q, _ = jnp.linalg.qr(y)
    b = mm(q.T, g, prec)
    return q, mm(b, b.T, prec), k_sample


def small_svd(gram: np.ndarray):
    """Left singular vectors and values of B from its Gram matrix B B^T,
    in float64 on the host, largest first."""
    w, vecs = np.linalg.eigh(gram.astype(np.float64))
    order = np.argsort(w)[::-1]
    return (vecs[:, order].astype(np.float32),
            np.sqrt(np.maximum(w[order], 0.0)).astype(np.float32))


def sara_select(q, ub, s, key, r, k):
    """SARA: r of the top-k singular directions U = Q ub, drawn without
    replacement with probability proportional to the singular values
    (Gumbel top-k), kept in ascending order."""
    u, s = mm(q, ub[:, :k]), s[:k]
    w = jnp.where(jnp.sum(s) > 0, s, jnp.ones_like(s))
    logw = jnp.where(w > 0, jnp.log(jnp.maximum(w, 1e-38)), -1e30)
    scores = logw + jax.random.gumbel(key, (k,), jnp.float32)
    idx = jnp.sort(jax.lax.top_k(scores, r)[1])
    return jnp.take(u, idx, axis=-1)


def learning_rate(done, oc):
    """The paper's schedule at ``done`` updates already applied: linear
    warmup from 0 to the peak, then cosine decay to a tenth of it."""
    done = done.astype(jnp.float32)
    peak, warm, total = oc["lr"], oc["warmup_steps"], oc["total_steps"]
    frac = jnp.clip((done - warm) / (total - warm), 0.0, 1.0)
    decay = peak * (0.1 + 0.9 * 0.5 * (1.0 + jnp.cos(math.pi * frac)))
    return jnp.where(done < warm, peak * done / warm, decay)


def clip(grads, max_norm: float):
    """The gradients scaled to a global norm of at most ``max_norm``."""
    leaves = jax.tree_util.tree_leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-12))
    return jax.tree_util.tree_map(lambda g: g * scale, grads)


def opt_step(params, state: OptState, grads, oc, new_bases=None):
    """One Adam step in each leaf's subspace on clipped ``grads`` (as
    ``clip`` gives them); ``new_bases`` (one projector stack per low-rank
    leaf, None elsewhere) makes it a refresh step."""
    step = state.step + 1
    t = step.astype(jnp.float32)
    b1, b2, eps = oc["b1"], oc["b2"], oc["eps"]
    lr = learning_rate(state.step, oc)
    key = state.key
    if new_bases is not None:
        key, _ = jax.random.split(key)
    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    out_p, out_bases, out_m, out_v = [], [], [], []

    def adam(m, v, r):
        m = b1 * m + (1 - b1) * r
        v = b2 * v + (1 - b2) * r * r
        return m, v, (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)

    for i, (w, g) in enumerate(zip(flat_p, flat_g)):
        p, m, v = state.proj[i], state.m[i], state.v[i]
        if p is None:
            m, v, n = adam(m, v, g)
            out_p.append(w - lr * n)
        else:
            left = _left(w.shape)
            go = _orient(_slices(g), left)
            if new_bases is not None:
                p = new_bases[i]
            m, v, n = adam(m, v, ein("ldr,ldn->lrn", p, go))
            wo = (_orient(_slices(w), left)
                  - lr * oc["alpha"] * ein("ldr,lrn->ldn", p, n))
            out_p.append(_orient(wo, left).reshape(w.shape))
        out_bases.append(p)
        out_m.append(m)
        out_v.append(v)
    return (jax.tree_util.tree_unflatten(treedef, out_p),
            OptState(step, key, tuple(out_bases), tuple(out_m), tuple(out_v)))


# ---------------------------------------------------------------------------
# readings: what the correctness check compares
# ---------------------------------------------------------------------------


def slice_norms(x: jax.Array, stack: int) -> jax.Array:
    """Frobenius norm of each matrix of a leaf whose first ``stack`` axes
    stack them (row-major), or of the leaf where ``stack`` is 0."""
    x = x.astype(jnp.float32)
    if stack:
        return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(stack, x.ndim)))
                        ).reshape(-1)
    return jnp.sqrt(jnp.sum(x * x))[None]


def slice_sums(x: jax.Array, stack: int) -> jax.Array:
    """Sum of each matrix of a stack, as ``slice_norms`` takes them."""
    x = x.astype(jnp.float32)
    if stack:
        return jnp.sum(x, axis=tuple(range(stack, x.ndim))).reshape(-1)
    return jnp.sum(x)[None]


def moment_readings(ms, vs, stacks, b1: float):
    """Per slice of each leaf: the first moment's norm over (1 - b1), which
    after one step is the norm of the gradient as the optimizer got it
    (projected, for a low-rank leaf), and the second moment's sum;
    ``stacks`` gives each moment's stack axes."""
    return ([slice_norms(m, s) / (1.0 - b1) for m, s in zip(ms, stacks)],
            [slice_sums(v, s) for v, s in zip(vs, stacks)])


def second_grad_norms(v0_sums, v1_sums, b2: float) -> List[np.ndarray]:
    """Norm of the second step's gradient as the optimizer got it, from
    the second moment's sums after steps 0 and 1 (v1 = b2 v0 + (1 - b2)
    R1^2); after a refresh at step 1, R1 is taken in the new subspace."""
    return [np.sqrt(np.maximum(np.asarray(s1, np.float64)
                               - b2 * np.asarray(s0, np.float64), 0.0)
                    / (1.0 - b2))
            for s0, s1 in zip(v0_sums, v1_sums)]


def named_norms(layout, arrays) -> Dict[str, float]:
    """{"<path>[<slice>]": norm} (``<path>`` alone for a leaf that is no
    stack) from per-leaf norm vectors."""
    out = {}
    for leaf, vals in zip(layout, arrays):
        vals = np.asarray(vals)
        for i, val in enumerate(vals):
            out[f"{leaf.path}[{i}]" if leaf.stack else leaf.path] = float(val)
    return out


def optimizer_constants(config: Dict[str, Any]) -> Dict[str, Any]:
    oc = dict(config["optimizer"])
    if "rank" in config:  # rehearsal override
        oc["rank"] = config["rank"]
    return oc


class Reference:
    """Runs the reference from the seed's weights over the first steps and
    returns the readings the check compares."""

    def __init__(self, config: Dict[str, Any], prec: str = "f32",
                 redraw: bool = False, signs: bool = False):
        """For the check's calibration: ``redraw`` is a fault, every refresh
        after the first draws from another key than the schedule's;
        ``signs`` a witness, the small SVD's vectors take other signs (as
        another SVD routine may give them)."""
        self.config = config
        self.redraw = redraw
        self.signs = np.random.default_rng(1) if signs else None
        self.layout = weights_lib.leaves(config)
        fam = families.load(config)
        c = fam.constants(config)
        oc = optimizer_constants(config)
        self.oc = oc
        # the moments of a low-rank leaf are one stack of slices
        stacks = [1 if x.lowrank else x.stack for x in self.layout]

        def vg(params, tokens, labels):
            with jax.default_matmul_precision("highest"):
                return jax.value_and_grad(fam.loss)(params, tokens, labels, c,
                                                    prec)

        self._vg = jax.jit(vg)

        def step(params, state, grads, new_bases):
            with jax.default_matmul_precision("highest"):
                return opt_step(params, state, grads, oc, new_bases)

        self._step = jax.jit(step, donate_argnums=(0, 1, 2))
        self._clip = jax.jit(functools.partial(clip,
                                               max_norm=oc["grad_clip_norm"]),
                             donate_argnums=0)

        @functools.partial(jax.jit, static_argnums=(1, 3))
        def sketch_leaf(g, left, keys, r):
            with jax.default_matmul_precision("highest"):
                return jax.vmap(lambda gg, kk: sketch(
                    gg, kk, r, oc, prec if ROUND[prec][2] else "f32"))(
                    _orient(g, left), keys)

        self._sketch = sketch_leaf

        @functools.partial(jax.jit, static_argnums=(4, 5))
        def select_leaf(q, ub, s, keys, r, k):
            with jax.default_matmul_precision("highest"):
                return jax.vmap(
                    lambda *a: sara_select(*a, r, k))(q, ub, s, keys)

        self._select = select_leaf

        self._moments = jax.jit(lambda state: moment_readings(
            state.m, state.v, stacks, oc["b1"]))

    def refresh(self, grads, state: OptState):
        """New projectors of every low-rank leaf from this step's grads."""
        _, sub = jax.random.split(state.key)
        if self.redraw and int(state.step) > 0:
            sub = jax.random.fold_in(sub, 1)
        flat_g = jax.tree_util.tree_leaves(grads)
        out = []
        for i, (g, p) in enumerate(zip(flat_g, state.proj)):
            if p is None:
                out.append(None)
                continue
            d, r = p.shape[-2:]
            k = min(self.oc["sara_pool_factor"] * r, d)
            g = _slices(g)
            keys = jax.random.split(jax.random.fold_in(sub, i), g.shape[0])
            q, gram, k_sample = self._sketch(g, g.shape[-2] <= g.shape[-1],
                                             keys, r)
            with concurrent.futures.ThreadPoolExecutor(EIGH_THREADS) as ex:
                svds = list(ex.map(small_svd, np.asarray(gram)))
            if self.signs is not None:
                svds = [(u * self.signs.choice([-1.0, 1.0], u.shape[1]).astype(
                    np.float32), v) for u, v in svds]
            ub = jnp.asarray(np.stack([u for u, _ in svds]))
            s = jnp.asarray(np.stack([v for _, v in svds]))
            out.append(self._select(q, ub, s, k_sample, r, k))
        return tuple(out)

    def run(self, key, opt_seed: int, batches: List[Dict[str, np.ndarray]],
            tau: int) -> Dict[str, Any]:
        params = weights_lib.init(key, self.config)
        state = opt_init(params, self.oc, opt_seed, self.layout)
        if len(batches) < 2:
            raise ValueError("the check reads the first two steps")
        losses, readings = [], []
        for s, batch in enumerate(batches):
            lval, grads = self._vg(params, jnp.asarray(batch["tokens"]),
                                   jnp.asarray(batch["labels"]))
            grads = self._clip(grads)
            losses.append(float(lval))
            new_bases = self.refresh(grads, state) if s % tau == 0 else None
            params, state = self._step(params, state, grads, new_bases)
            if s < 2:
                readings.append(jax.device_get(self._moments(state)))
        del state
        change = change_norms(key, self.config, params)
        (norms, v0), (_, v1) = readings
        return {"losses": losses,
                "grad_norms": named_norms(self.layout, norms),
                "second_grad_norms": named_norms(
                    self.layout, second_grad_norms(v0, v1, self.oc["b2"])),
                "change_norms": change}


@functools.lru_cache(maxsize=None)
def _change_fn(index: int, shape: Tuple[int, ...], ones: bool, stack: int):
    def f(key, w):
        return slice_norms(w - weights_lib.leaf_value(key, index, shape, ones),
                           stack)

    return jax.jit(f)


def change_norms(key, config, params) -> Dict[str, float]:
    """Per slice, the norm of the change of the weights from the seed's:
    each leaf's initial value is made again on its own, one at a time."""
    layout = weights_lib.leaves(config)
    flat = jax.tree_util.tree_leaves(params)
    vals = [_change_fn(i, x.shape, x.ones, x.stack)(key, w)
            for i, (x, w) in enumerate(zip(layout, flat))]
    return named_norms(layout, vals)
