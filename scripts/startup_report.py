"""Where a run's start-up goes, from the program's own host record
(``eventstreamgpt_tpu/utils/scopes.py``): self seconds by phase, compile spans by program.

    python scripts/startup_report.py --workload <cell>
    python scripts/startup_report.py --log <save_dir>/train_log.jsonl

``--workload`` runs ``benchmark/run.py``'s ``main`` in this process (on the chip,
as that does, one seed, the benchmark's ten seconds) and adds what no program
span covers, between the harness's own stamps: the rows sum to the run's ``setup_s``. ``--log`` prints the ``startup``
line `train()` wrote, and its ``compile`` lines after it.
"""

import argparse
import contextlib
import io
import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
ROW = "{:<44}{:>9}{:>9}{:>9}{:>11}{:>9}{:>6}"


def per_event(phase: str, seconds: float, counts: dict) -> str:
    """What a phase that counts events cost for its size: the dataset read's
    events, and microseconds an event."""
    n = counts.get(phase, {}).get("events")
    return f"  {n:,} events, {1e6 * seconds / n:.2f} us an event" if n else ""


def table(summary: dict) -> str:
    counts = summary.get("counts", {})  # a train_log.jsonl written before phases carried counts has none
    lines = [ROW.format("phase", "self_s", *[""] * 5)]
    lines += [ROW.format(name, f"{s:.2f}", *[""] * 5).rstrip() + per_event(name, s, counts) for name, s in summary["phases"].items()]
    lines.append(ROW.format("compiled program", "trace", "lower", "backend", "cache_load", "compiles", "hits"))
    for program, r in summary["compile"].items():
        times = [f"{r[k]:.2f}" for k in ("trace", "lower", "backend", "cache_load")]
        lines.append(ROW.format(program[:43], *times, r["compiles"], r["hits"]))
    return "\n".join(lines)


class Tee(io.TextIOBase):
    def __init__(self, *to):
        self.write = lambda text: max(stream.write(text) for stream in to)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--log")
    args = ap.parse_args(argv)
    if args.log:
        for rec in map(json.loads, Path(args.log).read_text().splitlines()):
            if "startup" in rec:
                print(table(rec.pop("startup")), json.dumps(rec), sep="\n")
            elif "compile" in rec:
                print(json.dumps(rec))
        return 0
    from benchmark import run as bench
    from eventstreamgpt_tpu.utils import scopes

    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(Tee(sys.stdout, out)), contextlib.redirect_stderr(Tee(sys.stderr, err)):
        bench.main(["--workload", args.workload, "--seed", "3600000001", "--seconds", "10"])
    setup_s = json.loads(out.getvalue().splitlines()[-1])["metrics"]["setup_s"]["value"]
    t0 = bench._T_PROCESS  # the clock of the harness's stamps and of setup_s
    spans = [s for s in scopes.recorded() if s.end <= t0 + setup_s]
    print(f"start-up of {args.workload}: setup_s {setup_s:.2f}, cache {scopes.compile_totals()}", table(scopes.summary(spans)), sep="\n")
    stamps = [(float(m[1]), m[2]) for m in re.finditer(r"^\[ *([0-9.]+)s\] (.*)$", err.getvalue(), re.M)]
    stamps = [(0.0, ""), *(s for s in stamps if s[0] < setup_s), (setup_s, "the window starts")]
    print("under no program span (the harness's own work), up to each of its stamps: seconds, of the stretch")
    for (a, _), (b, what) in zip(stamps, stamps[1:]):
        under = sum(max(0.0, min(t0 + b, s.end) - max(t0 + a, s.start)) for s in spans if s.parent is None)
        print(f"  {what[:60]:<62}{b - a - under:9.2f}{b - a:9.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
