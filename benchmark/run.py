"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process on the machine it is started on. It exits non-zero and prints no
result line when JAX finds no TPU (or fewer chips than the cell asks for), or
when the program is not beside it. The last line of standard output is the
result as one JSON object; the numbers compared for ``correct`` are printed
beside their limits as the last lines of standard error and under the result's
last key.

``--mode`` (not used by the driver) reads the limits' two ends on the chip:
``control`` puts the reference in the program's place at the precision below
the configuration's, ``fault:<name>`` plants one of the job's faults there.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
if str(CHECKOUT) not in sys.path:
    sys.path.insert(0, str(CHECKOUT))

from benchmark.harness import device, loader, meter, trace as trace_lib  # noqa: E402


class RunEnv:
    """What a job module gets from the harness for one run."""

    def __init__(self, cell: dict, chips: int, tracing: bool, work_dir: Path):
        self.cell = cell
        self.chips = chips
        self.work_dir = work_dir
        self.spans = meter.Spans(annotate=tracing)
        self.meter = meter.CompileMeter()
        self.reference = loader.load_reference(cell)
        self.window_start: float | None = None
        self.trace_dir = work_dir / "trace"
        self.trace_window: tuple[float, float] | None = None

    def memory_peak(self) -> int:
        return device.memory_peak_bytes(self.chips)

    def log(self, what: str) -> None:
        """A timestamped line on standard error: where set-up's seconds go."""
        print(f"[{time.perf_counter() - _T_PROCESS:8.2f}s] {what}", file=sys.stderr, flush=True)

    def start_trace(self) -> None:
        import jax

        self._t_trace = time.perf_counter()
        jax.profiler.start_trace(str(self.trace_dir))

    def stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self.trace_window = (self._t_trace, time.perf_counter())


def configure_compile_cache() -> None:
    """JAX's persistent cache at a fixed place inside the checkout, unless the
    machine names one; small programs are cached too, so that a second run of
    a cell compiles nothing."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # No eviction: a cell's step program alone is some hundred MiB, and a
    # cache that drops it makes every run compile.
    jax.config.update("jax_compilation_cache_max_size", -1)


def per_layer_metrics(cell: dict, record: dict) -> dict:
    out = {}
    for name, module in loader.metric_readers().items():
        value = module.read(record)
        if value is not None:
            out[name] = {"value": value, "unit": module.UNIT}
    return out


def result_line(cell: dict, record: dict, dev: dict, tracing: bool, setup_s: float) -> dict:
    dev = dict(dev, memory_peak_bytes=record["memory_peak_bytes"])
    line = {
        "correct": bool(record["correct"]),
        "attempted": record["attempted"],
        "failed": record["failed"],
    }
    if tracing:
        line["metrics"] = per_layer_metrics(cell, record)
        dev["busy_s"] = record["trace"]["busy_s"]
        dev["window_s"] = record["trace"]["window_s"]
        line["breakdown"] = record["trace"]["breakdown"]
    else:
        units = record["end_to_end_units"]
        line["metrics"] = {
            name: {"value": value, "unit": units[name]} for name, value in record["end_to_end"].items()
        }
        line["metrics"]["setup_s"] = {"value": setup_s, "unit": "s"}
    line["device"] = dev
    line["compared"] = record["compared"]
    return line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-summary", default=None, help="write what the trace holds (planes, lines, names) here")
    ap.add_argument("--trace-sample", default=None, help="write a small recorded piece of the trace here")
    args = ap.parse_args(argv)

    cell = loader.load_cell(args.workload)
    import eventstreamgpt_tpu  # noqa: F401  (the system under test has to be beside the benchmark)

    dev = device.require_tpu(cell["chips"])
    configure_compile_cache()
    work_dir = CHECKOUT / ".bench_work" / f"{args.workload}.{os.getpid()}"
    shutil.rmtree(work_dir, ignore_errors=True)
    work_dir.mkdir(parents=True)
    tracing = bool(args.trace)
    try:
        env = RunEnv(cell, cell["chips"], tracing, work_dir)
        record = loader.load_job(cell).run(cell, args.seed, args.seconds, tracing, env)
        setup_s = env.window_start - _T_PROCESS
        if tracing:
            events = trace_lib.read_xplane(env.trace_dir)
            if args.trace_summary:
                Path(args.trace_summary).write_text(json.dumps(trace_lib.describe(events), indent=1))
            if args.trace_sample:
                Path(args.trace_sample).write_text(json.dumps(trace_lib.sample_around_second_module(events)))
            summary = trace_lib.summarize(events, cell["chips"])
            summary["window_s"] = record["window"][1] - record["window"][0]
            record["trace"] = summary
            record["spans"] = env.spans
            record["device_kind"] = dev["kind"]
        line = result_line(cell, record, dev, tracing, setup_s)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    print(f"setup_s {setup_s:.3f} memory_peak_bytes {record['memory_peak_bytes']}", file=sys.stderr)
    for name, number in record["compared"].items():
        print(f"compared {name} {json.dumps(number)}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except device.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        code = 3
    sys.exit(code)
