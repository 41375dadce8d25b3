"""Reads the two ends of a training cell's limits, on the chip.

    python3 benchmark/check_limits.py --workload <cell> --seeds 11 12 13 \
        [--control-seeds 3] [--fault-seeds 3] [--out chiprun_out/limits.jsonl]

For every seed, in one process: the program's first dispatch against the
plain reference (the lower reading); for the first ``--control-seeds`` seeds
the reference in the program's place computed in fp8 (the control: the upper
reading); for the first ``--fault-seeds`` seeds the reference in the
program's place with each fault of `pretrain.FAULTS` planted. No window is
measured: a training cell's readings need none. The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
if str(CHECKOUT) not in sys.path:
    sys.path.insert(0, str(CHECKOUT))

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import cohort as cohort_lib, device, loader  # noqa: E402


def gaps(job, cell, observed, ref, table: dict | None = None, tag: str = "") -> dict:
    verdict = job.compare(cell, observed, *ref)
    numbers = verdict["numbers"]
    out = {k: v["value"] for k, v in numbers.items()} | {
        "grad_leaf": numbers["grad_norm_gap"]["leaf"], "change_leaf": numbers["param_change_gap"]["leaf"],
        "diff_leaf": numbers["grad_diff_gap"]["leaf"], "losses": observed["losses"],
    }
    if table is not None:  # every leaf's readings, for a look at home
        table[tag] = {
            "losses": observed["losses"], "mu": observed["mu"], "delta": observed["delta"],
            "mu_diff": verdict["leaf_differences"],
        }
    return out


def main(argv=None, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    ap.add_argument("--stated-seeds", type=int, default=0,
                    help="for this many seeds also the reference computed in bfloat16, the stated precision")
    ap.add_argument("--leaf-tables", default=None, help="write every leaf's readings of every seed here (JSON lines)")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override a key of the configuration's `config` (another path of the program as a witness)")
    ap.add_argument("--cell", action="append", default=[], metavar="SECTION.KEY=JSON",
                    help="override a key of the cell's file, as feed.steps_per_dispatch=1 (a look, never a reading)")
    ap.add_argument("--witness-fp32", action="store_true",
                    help="run the program in float32 at the highest matmul precision: a second witness")
    args = ap.parse_args(argv)
    cell = loader.load_cell(args.workload)
    for item in args.set:
        key, _, value = item.partition("=")
        cell["model"]["config"][key] = json.loads(value)
    for item in args.cell:
        key, _, value = item.partition("=")
        section, _, name = key.partition(".")
        cell[section][name] = json.loads(value)
    if args.witness_fp32:
        cell["model"]["config"]["precision"] = "fp32"
    if require_chip:
        device.require_tpu(cell["chips"])
    import contextlib

    import jax

    bench_run.configure_compile_cache()
    job = loader.load_job(cell)
    reference = loader.load_reference(cell)
    work = CHECKOUT / ".bench_work" / f"limits.{args.workload}"
    out = open(args.out, "a") if args.out else None
    tables = open(args.leaf_tables, "a") if args.leaf_tables else None
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        shutil.rmtree(work, ignore_errors=True)
        work.mkdir(parents=True)
        cohort = cohort_lib.make_cohort(cell["cohort"], seed)
        prog = job.Program(cell, cohort, reference, seed, work)
        plans, _ = prog.next_plans()
        highest = jax.default_matmul_precision("highest") if args.witness_fp32 else contextlib.nullcontext()
        with highest:
            observed = {"losses": [float(x) for x in prog.dispatch(plans)]}
        observed.update(prog.observed_norms())
        sizes = prog.model_sizes
        prog.free()
        del prog
        ref = job.follow(cell, cohort, reference, sizes, plans, seed)
        table = {"seed": seed, "reference": {"losses": ref[0], "delta": ref[1], "mu": ref[2]}} if tables else None
        row = {"seed": seed, "program": gaps(job, cell, observed, ref, table, "program"), "ref_losses": ref[0]}
        del observed
        as_observed = lambda r: {"losses": r[0], "delta": r[1], "mu": r[2], "mu_tensors": r[3]}  # noqa: E731
        if i < args.stated_seeds:
            got = job.follow(cell, cohort, reference, sizes, plans, seed, quant=reference.bf16_operand)
            row["stated_bf16"] = gaps(job, cell, as_observed(got), ref, table, "stated_bf16")
        if i < args.control_seeds:
            got = job.follow(cell, cohort, reference, sizes, plans, seed, quant=reference.fp8_operand)
            row["control_fp8"] = gaps(job, cell, as_observed(got), ref, table, "control_fp8")
        if i < args.fault_seeds:
            for fault in job.FAULTS:
                got = job.follow(cell, cohort, reference, sizes, plans, seed, fault=fault)
                row[f"fault_{fault}"] = gaps(job, cell, as_observed(got), ref, table, f"fault_{fault}")
        row["seconds"] = time.perf_counter() - t0
        if tables:
            tables.write(json.dumps(table) + "\n")
            tables.flush()
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()
    shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except device.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        sys.exit(3)
