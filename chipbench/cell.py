"""Finds a cell's files by name: its entry in BENCHMARK.json, its workload
file (traffic parameters and correctness limits), its configuration file
and the configuration's family module (``chipbench.families``).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict, List, Tuple

from chipbench import families

BENCH = Path(__file__).resolve().parent  # chipbench/
CHECKOUT = BENCH.parent

def _load(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]  # the configuration file, rehearsal applied
    traffic: Dict[str, Any]  # the workload file
    end_to_end: List[Dict[str, Any]]  # BENCHMARK.json entries it reports
    per_layer: List[Dict[str, Any]]
    rehearsal: bool

    @property
    def seq_len(self) -> int:
        return int(self.config.get("seq_len", self.traffic["seq_len"]))

    @property
    def vocab(self) -> int:
        return int(families.load(self.config).vocab(self.config))

    @property
    def batch(self) -> int:
        return int(self.traffic["batch"])

    @property
    def tokens_per_step(self) -> int:
        return self.batch * self.seq_len

    @property
    def tau(self) -> int:
        return int(self.traffic["tau"])

    @property
    def check_steps(self) -> int:
        return int(self.traffic["check_steps"])


def _reports(entry: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, *, rehearsal: bool = False) -> Cell:
    """The cell ``name`` as BENCHMARK.json lists it.  ``rehearsal`` applies
    the configuration's ``rehearsal`` overrides (a tiny size for the CPU)."""
    bench = _load(CHECKOUT / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SystemExit(
            f"unknown workload {name!r}; BENCHMARK.json lists "
            f"{sorted(entries)}"
        )
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    traffic = _load(BENCH / "workloads" / f"{name}.json")
    if traffic["config"] != entry["config"]:
        raise SystemExit(
            f"{name}: workload file names config {traffic['config']!r}, "
            f"BENCHMARK.json {entry['config']!r}"
        )
    config = _load(CHECKOUT / configs[entry["config"]]["file"])
    families.load(config)  # an unknown family fails before the chip is touched
    if rehearsal:
        config = {**config, **config["rehearsal"]}
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        rehearsal=rehearsal,
    )


def model_config(cell: Cell):
    """The program's ModelConfig for the cell: the registry entry's
    defaults with every size of the configuration file set on it."""
    from repro.configs.registry import get_config

    base = get_config(cell.config["registry"])
    keys = families.load(cell.config).KEYS
    kw = {field: cell.config[key] for key, field in keys.items()}
    if cell.rehearsal:
        kw.update(loss_chunk=32, attn_chunk_q=16, attn_chunk_kv=16)
    return base.with_(**kw)


def optimizer_kwargs(cell: Cell, seed: int) -> Tuple[str, Dict[str, Any]]:
    """Keyword arguments of ``repro.core.make_optimizer`` for the cell."""
    opt = dict(cell.config["optimizer"])
    kw = {k: opt[k] for k in (
        "engine", "svd_backend", "rank", "sara_pool_factor", "svd_oversample",
        "svd_power_iters", "alpha", "lr", "b1", "b2", "eps",
        "momentum_carry", "refresh_groups", "grad_clip_norm",
    )}
    if "rank" in cell.config:  # rehearsal override
        kw["rank"] = cell.config["rank"]
    if opt["lr_schedule"] != "cosine_with_warmup":
        raise SystemExit(f"unknown lr_schedule {opt['lr_schedule']!r}")
    from repro.core.schedules import cosine_with_warmup

    kw["lr_schedule"] = cosine_with_warmup(
        opt["lr"], opt["warmup_steps"], opt["total_steps"])
    kw["tau"] = cell.tau
    kw["seed"] = seed
    return opt["name"], kw
