"""Roofline machinery: HLO collective parsing, corrections, report math."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SHAPES
from repro.configs.registry import get_config
from repro.roofline import hw
from repro.roofline.analysis import (
    collective_stats,
    model_bytes,
    model_flops,
    scan_corrections,
)

HLO_SAMPLE = """
HloModule test
ENTRY main {
  %p = f32[128,256]{1,0} parameter(0)
  %ag = f32[128,1024]{0,1} all-gather(%p), channel_id=1, replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={1}
  %ar = f32[128,1024]{1,0} all-reduce(%ag), channel_id=2, replica_groups={{0,1},{2,3},{4,5},{6,7}}, to_apply=%add
  %rs = bf16[64,256]{1,0} reduce-scatter(%something), channel_id=3, replica_groups=[2,4]<=[8], dimensions={0}
  %cp = f32[32,32]{1,0} collective-permute(%x), channel_id=4, source_target_pairs={{0,1}}
  %a2a = f32[16,64]{1,0} all-to-all(%y), channel_id=5, replica_groups={{0,1,2,3}}
  %ar2 = f32[8]{0} all-reduce-start(%z), channel_id=6, replica_groups={{0,1}}
  %ard = f32[8]{0} all-reduce-done(%ar2)
}
"""


def test_collective_parsing_kinds_and_bytes():
    st = collective_stats(HLO_SAMPLE)
    c = st["count_by_kind"]
    assert c["all-gather"] == 1
    assert c["all-reduce"] == 2  # plain + -start (done skipped)
    assert c["reduce-scatter"] == 1
    assert c["collective-permute"] == 1
    assert c["all-to-all"] == 1
    b = st["bytes_by_kind"]
    # all-gather: result/g = 128*1024*4/4
    assert b["all-gather"] == 128 * 1024 * 4 / 4
    # all-reduce: result bytes (+ the tiny -start one)
    assert b["all-reduce"] == 128 * 1024 * 4 + 8 * 4
    # reduce-scatter iota groups [2,4]: g=4 -> result*4
    assert b["reduce-scatter"] == 64 * 256 * 2 * 4
    assert st["total_bytes"] > 0


def test_collective_parsing_on_real_module():
    """Sharded matmul HLO must yield nonzero parsed collective bytes."""
    import subprocess
    import sys
    import os

    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.roofline.analysis import collective_stats
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2))
xs = NamedSharding(mesh, P("data", None))
ws = NamedSharding(mesh, P(None, "model"))
def f(a, w):
    y = a @ w
    return jax.lax.with_sharding_constraint(
        y, NamedSharding(mesh, P("data", None))) @ w.T
a = jax.ShapeDtypeStruct((256, 512), jnp.float32)
w = jax.ShapeDtypeStruct((512, 512), jnp.float32)
c = jax.jit(f, in_shardings=(xs, ws)).lower(a, w).compile()
st = collective_stats(c.as_text())
assert st["total_bytes"] > 0, st
print("OK", st["total_bytes"])
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env=env,
    )
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


def test_model_flops_sane():
    cfg = get_config("llama3-8b")
    n = 8_030_000_000
    tr = model_flops(cfg, SHAPES["train_4k"], n)
    # >= 6 N D
    assert tr >= 6 * n * SHAPES["train_4k"].global_batch * 4096
    pf = model_flops(cfg, SHAPES["prefill_32k"], n)
    assert pf > 2 * n * SHAPES["prefill_32k"].global_batch * 32768
    dec = model_flops(cfg, SHAPES["decode_32k"], n)
    assert dec < tr / 100  # one token per sequence


def test_model_flops_moe_active():
    cfg = get_config("deepseek-moe-16b")
    n = 16_900_000_000
    tr = model_flops(cfg, SHAPES["train_4k"], n)
    dense_equiv = 6 * n * SHAPES["train_4k"].global_batch * 4096
    assert tr < 0.5 * dense_equiv  # top-6 of 64 experts


def test_model_bytes_decode_includes_cache():
    cfg = get_config("llama3-8b")
    n = 8_030_000_000
    dec = model_bytes(cfg, SHAPES["decode_32k"], n)
    cache = 2 * 32 * 128 * 32768 * cfg.kv_dim * 2
    assert dec > cache  # params + cache


def test_scan_corrections_families():
    cfg = get_config("llama3-8b")
    corr = scan_corrections(cfg, SHAPES["prefill_32k"])
    assert "attn_chunks" in corr  # 32k -> chunked
    assert "loss_chunks" not in corr  # prefill: no loss
    corr_t = scan_corrections(cfg, SHAPES["train_4k"])
    assert "loss_chunks" in corr_t
    cfg_m = get_config("mamba2-370m")
    corr_m = scan_corrections(cfg_m, SHAPES["train_4k"])
    assert "ssd_chunks" in corr_m
    assert "attn_chunks" not in corr_m


def test_hw_constants():
    assert hw.PEAK_FLOPS_BF16 == 197e12
    assert hw.HBM_BW == 819e9
    assert hw.ICI_LINK_BW == 50e9


def test_pallas_kernel_counts_groups_by_wrapper():
    from repro.roofline.analysis import pallas_kernel_counts

    def line(op_name):
        return (f'  %k = f32[8,128] custom-call(%a), custom_call_target='
                f'"tpu_custom_call", metadata={{op_name="{op_name}"}}')

    hlo = "\n".join([
        line("jit(step)/jit(lowrank_adam_update_batched)/pallas_call"),
        line("jit(step)/while/body/jit(lowrank_adam_update_batched)"
             "/pallas_call"),
        line("jit(loss)/jvp(jit(flash_attention_fwd))/pallas_call"),
        '  %c = f32[8] custom-call(%a), custom_call_target="Sharding"',
    ])
    assert pallas_kernel_counts(hlo) == {
        "lowrank_adam_update_batched": 2, "flash_attention_fwd": 1,
    }
