"""Backend dispatch for the fused RMSNorm kernel.

Same contract as the other kernel families (lowrank_update, galore_project,
power_iter):

* TPU backend: the Pallas kernel (kernel.py) -- one HBM read + one write
  per row block instead of the three passes of the unfused form.
* everywhere else: the pure-jnp reference (ref.py) -- identical math (fp32
  statistics, input-dtype output), so models are backend-agnostic and CI
  proves kernel parity in interpret mode.

``models/layers.rmsnorm`` routes through here, so every architecture in
models/ picks up the fused kernel on TPU without touching model code.  The
kernel path is a ``custom_vjp``: the Pallas forward has no autodiff rule,
so the backward recomputes through the reference's VJP.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.rmsnorm import kernel as kernel_lib
from repro.kernels.rmsnorm.ref import rmsnorm_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def rmsnorm(
    x: jax.Array,  # (..., D)
    scale: jax.Array,  # (D,)
    eps: float = 1e-5,
    *,
    force_pallas: bool = False,
    interpret: bool = False,
) -> jax.Array:
    if force_pallas or _on_tpu():
        return _rmsnorm_kernel(x, scale, eps, interpret or not _on_tpu())
    return rmsnorm_ref(x, scale, eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rmsnorm_kernel(x, scale, eps: float, interpret: bool):
    return kernel_lib.rmsnorm(x, scale, eps=eps, interpret=interpret)


def _rmsnorm_fwd(x, scale, eps, interpret):
    return _rmsnorm_kernel(x, scale, eps, interpret), (x, scale)


def _rmsnorm_bwd(eps, interpret, res, g):
    x, scale = res
    _, vjp = jax.vjp(lambda x_, s_: rmsnorm_ref(x_, s_, eps), x, scale)
    return vjp(g)


_rmsnorm_kernel.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)
