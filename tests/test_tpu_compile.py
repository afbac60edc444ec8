"""Compile every Pallas kernel of the training and serving paths with the
TPU compiler, for a described (not attached) TPU v5e, at Qwen2-1.5B shapes.

Interpret mode (the rest of the suite) cannot see what Mosaic refuses:
unaligned block shapes, ops it cannot legalize, VMEM over-use.  These tests
lower and compile each kernel for one chip of a ``v5e:2x2`` topology and
check that the compiled program holds the kernel's ``tpu_custom_call``.
Nothing runs, so no numbers are checked here.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and every test
worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# Qwen2-1.5B widths: d_model 1536, d_ff 8960, 12 query / 2 KV heads of 128;
# rank 384 (the chip smoke's rank), B = 4 stacked leaves per bucket.
D_MODEL, D_FF, RANK, B = 1536, 8960, 384, 4
SEQ, HEADS, KV_HEADS, HEAD_DIM = 4096, 12, 2, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler library in this install
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)


def _compile(fn, args, sharding, kernel):
    """Lower + compile ``fn`` for the described chip and check that the
    program holds the Pallas kernel emitted by the wrapper ``kernel``."""
    from repro.roofline.analysis import pallas_kernel_counts

    specs = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in args
    ]
    compiled = jax.jit(fn).lower(*specs).compile()
    counts = pallas_kernel_counts(compiled.as_text())
    assert counts.get(kernel, 0) >= 1, counts


F32, BF16, I32, U8 = jnp.float32, jnp.bfloat16, jnp.int32, jnp.uint8
SCALAR_F32, SCALAR_I32 = ((), F32), ((), I32)


def test_lowrank_adam_update_batched(one_chip):
    from repro.kernels.lowrank_update.kernel import lowrank_adam_update_batched

    def f(w, p, r, m, v, step, lr):
        return lowrank_adam_update_batched(w, p, r, m, v, step, lr)

    stack = ((B, RANK, D_FF), F32)
    _compile(f, [((B, D_MODEL, D_FF), F32), ((B, D_MODEL, RANK), F32),
                 stack, stack, stack, SCALAR_I32, SCALAR_F32], one_chip,
             "lowrank_adam_update_batched")


def test_lowrank_msgd_update_batched(one_chip):
    from repro.kernels.lowrank_update.kernel import lowrank_msgd_update_batched

    def f(w, p, r, m, lr):
        return lowrank_msgd_update_batched(w, p, r, m, lr)

    stack = ((B, RANK, D_FF), F32)
    _compile(f, [((B, D_MODEL, D_FF), F32), ((B, D_MODEL, RANK), F32),
                 stack, stack, SCALAR_F32], one_chip,
             "lowrank_msgd_update_batched")


@pytest.mark.parametrize("side", ["left", "right"])
def test_lowrank_adam_mini_update_batched(one_chip, side):
    from repro.kernels.lowrank_update.kernel import (
        lowrank_adam_mini_update_batched,
    )

    def f(w, p, r, m, v, step, lr):
        return lowrank_adam_mini_update_batched(
            w, p, r, m, v, step, lr, side=side
        )

    stack = ((B, RANK, D_FF), F32)
    v = ((B, RANK) if side == "left" else (B, D_FF), F32)
    _compile(f, [((B, D_MODEL, D_FF), F32), ((B, D_MODEL, RANK), F32),
                 stack, stack, v, SCALAR_I32, SCALAR_F32], one_chip,
             "lowrank_adam_mini_update_batched")


# 8-bit Adam: side='left' chunks run along n (the MLP bucket, n = d_ff);
# side='right' chunks run along r, which the kernel takes for r <= 256
# (the K/V bucket: d = 256 KV width, n = d_model, rank clamped to 256).
@pytest.mark.parametrize("side", ["left", "right"])
def test_lowrank_adam8bit_update_batched(one_chip, side):
    from repro.kernels.lowrank_update.kernel import (
        lowrank_adam8bit_update_batched,
    )
    from repro.kernels.lowrank_update.quantize import QBLOCK, num_blocks

    if side == "left":
        d, n, r = D_MODEL, D_FF, RANK
        scale = ((B, r, n // QBLOCK), F32)
    else:
        d, n, r = KV_HEADS * HEAD_DIM, D_MODEL, KV_HEADS * HEAD_DIM
        scale = ((B, n, num_blocks(r)), F32)

    def f(w, p, rg, mc, ms, vc, vs, step, lr):
        return lowrank_adam8bit_update_batched(
            w, p, rg, mc, ms, vc, vs, step, lr, side=side
        )

    codes = ((B, r, n), U8)
    _compile(f, [((B, d, n), F32), ((B, d, r), F32), ((B, r, n), F32),
                 codes, scale, codes, scale, SCALAR_I32, SCALAR_F32],
             one_chip, "lowrank_adam8bit_update_batched")


def test_galore_project_batched(one_chip):
    from repro.kernels.galore_project.kernel import galore_project_batched

    _compile(galore_project_batched,
             [((B, D_MODEL, D_FF), F32), ((B, D_MODEL, RANK), F32)],
             one_chip, "galore_project_batched")


def test_power_iter_batched(one_chip):
    from repro.core.svd import clamp_sketch
    from repro.kernels.power_iter.kernel import power_iter_batched

    # the attention-square bucket under SARA with a pool of 2 x rank (the
    # chip smoke's refresh): its (n, k') Z scratch fits the VMEM budget
    # that kernels/power_iter/ops.py gates on
    _, kp, _ = clamp_sketch(D_MODEL, D_MODEL, 2 * RANK, 8, 2)
    _compile(power_iter_batched,
             [((B, D_MODEL, D_MODEL), F32), ((B, D_MODEL, kp), F32)],
             one_chip, "power_iter_batched")


def test_rmsnorm(one_chip):
    from repro.kernels.rmsnorm.kernel import rmsnorm

    _compile(rmsnorm, [((1, SEQ, D_MODEL), BF16), ((D_MODEL,), F32)],
             one_chip, "rmsnorm")


def test_flash_attention_fwd_and_grad(one_chip):
    from repro.kernels.flash_attention.kernel import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, True, 0, 0, False)
        return jnp.sum(out.astype(jnp.float32))

    q = ((1, SEQ, HEADS, HEAD_DIM), BF16)
    kv = ((1, SEQ, KV_HEADS, HEAD_DIM), BF16)
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), [q, kv, kv],
             one_chip, "flash_attention_fwd")


def test_paged_decode_attention(one_chip):
    from repro.kernels.flash_attention_decode.kernel import (
        paged_decode_attention_kernel,
    )

    slots, page, pages_per_slot = 4, 16, SEQ // 16
    pool = slots * pages_per_slot
    _compile(paged_decode_attention_kernel,
             [((slots, 1, HEADS, HEAD_DIM), BF16),
              ((pool, page, KV_HEADS, HEAD_DIM), BF16),
              ((pool, page, KV_HEADS, HEAD_DIM), BF16),
              ((slots, pages_per_slot), I32), ((slots,), I32)],
             one_chip, "paged_decode_attention_kernel")
