"""TPU v5e per-chip constants for the analytic roofline model (published
figures, Google Cloud "TPU v5e" documentation).  These feed models and
dry-run estimates only; a measured run reads ``device_kind`` from JAX."""
from __future__ import annotations

PEAK_FLOPS_BF16 = 197e12  # per chip, bf16
HBM_BW = 819e9  # bytes/s per chip
ICI_LINK_BW = 50e9  # bytes/s per link (~)
VMEM_BYTES = 128 * 1024 * 1024  # ~128 MiB VMEM per chip (v5e)
HBM_BYTES = 16 * 1024**3  # 16 GiB HBM per chip

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
}
