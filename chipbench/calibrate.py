"""Readings that set the correctness limits: the program's own, through a
run's timed path, and the reference put in the program's place, computed
wrong on purpose, against the reference.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 [--variants fp8,half,redraw,signs,bf16]

Each seed runs the cell once (``run.run_cell`` with a short window: set-up,
the window's own call and feed, then the reference) and yields the
program's gaps as variant ``program``; each variant then runs in the
program's place against the same reference:

* ``fp8``: the control.  The configuration computes in bfloat16; the step
  below it is float8 (e4m3) inputs to every model matrix product.
* ``half``: a fault: the loss leaves the second half of every sequence out
  and averages over the rest.
* ``redraw``: a fault: every refresh after the first draws SARA's sample
  and sketch from another key than the schedule's.
* ``signs``: a witness, not a fault: the reference with the other sign
  of some of its small SVD's vectors, which another SVD routine may pick.
  With the moments kept at a refresh, a vector's sign decides whether its
  new gradient adds to the kept moment or takes from it.
* ``bf16``: a witness, not a fault: the reference with bfloat16 inputs to
  every model matrix product, as the configuration computes.  It reads
  what rounding alone does to each number.

A step that returns its state unchanged reads 1 by construction (its
gradient and change norms are 0) and needs no run.  Each line of output is
one JSON object: the cell, the seed, the variant and its gaps.  The
benchmark's own runs never run this.  Without a TPU it exits non-zero
unless ``--rehearsal`` is given (the tiny size on the CPU).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run  # noqa: E402

WINDOW_S = 1.0  # the program's window in a calibration run
PRECISION = {"fp8": "fp8", "bf16": "bf16"}


def readings(workload: str, seeds, variants, rehearsal: bool = False,
             program: bool = True):
    """Yield {"workload", "seed", "variant", gaps...} per seed and variant;
    ``program`` adds the program's own run of each seed first."""
    from chipbench import cell as cell_lib
    from chipbench import check as check_lib
    from chipbench import reference as ref_lib
    from chipbench import traffic as traffic_lib
    from chipbench import weights as weights_lib

    cell = cell_lib.load_cell(workload, rehearsal=rehearsal)
    for seed in seeds:
        words = traffic_lib.seed_words(seed)
        key = weights_lib.make_key(words[:2])
        opt_seed = words[2] & 0x7FFFFFFF
        traffic = traffic_lib.Traffic(
            cell.traffic, vocab=cell.vocab, batch=cell.batch,
            seq_len=cell.seq_len, seed=seed)
        steps = range(cell.check_steps)
        full = [traffic.batch_at(s) for s in steps]
        if program:
            got: dict = {}
            run.run_cell(workload, seed, WINDOW_S, False, rehearsal=rehearsal,
                         readings=got)
            ref = got["reference"]
            yield {"workload": workload, "seed": seed, "variant": "program",
                   **got["found"]}
            del got
        else:
            ref = ref_lib.Reference(cell.config).run(key, opt_seed, full,
                                                     cell.tau)
        for variant in variants:
            gc.collect()
            if variant in PRECISION:
                out = ref_lib.Reference(cell.config, PRECISION[variant]).run(
                    key, opt_seed, full, cell.tau)
            elif variant == "redraw":
                out = ref_lib.Reference(cell.config, redraw=True).run(
                    key, opt_seed, full, cell.tau)
            elif variant == "signs":
                out = ref_lib.Reference(cell.config, signs=True).run(
                    key, opt_seed, full, cell.tau)
            elif variant == "half":
                half = [traffic.batch_at(s, half=True) for s in steps]
                out = ref_lib.Reference(cell.config).run(
                    key, opt_seed, half, cell.tau)
            else:
                raise SystemExit(f"unknown variant {variant!r}")
            yield {"workload": workload, "seed": seed, "variant": variant,
                   **check_lib.gaps(out, ref)}
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="fp8,half,redraw,signs,bf16",
                    help="comma-separated; empty for the program alone")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    run.bootstrap()
    import jax

    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        raise SystemExit("no accelerator")
    seeds = [int(s) for s in args.seeds.split(",")]
    variants = [v for v in args.variants.split(",") if v]
    for row in readings(args.workload, seeds, variants, args.rehearsal):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
