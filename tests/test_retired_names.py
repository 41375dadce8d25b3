"""No living document or source root names what PR 30 retired.

`bench.py` with its timing protocol and pre-chip probes, the reviews it
answered to (``BASELINE.md``, ``VERDICT``, ``BENCH_rNN.json``, ``ADVICE``) and
the decode megakernel with the engine's option for it are gone; a comment or a
page that still cites one sends its reader to a file that is not there. The
histories (`ROADMAP.md`, `CHANGES.md`, `PERF.md`, `SURVEY.md`) and
`__graft_entry__.py`, whose citations PR 22 marked as history, are not targets.
"""

import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

RETIRED = re.compile(
    r"(?<![\w/])bench\.py"  # the root-level file, not e.g. microbench.py
    r"|utils[/.]benchmarking"
    r"|BASELINE\.md|VERDICT|BENCH_r|ADVICE"
    r"|pallas_decode_step|decode_step_impl"
    r"|probe_(?:feed|remat|scale|local_band|splash_blocks|na)\b"
    r"|profile_width"
)

DOCUMENTS = [
    "README.md",
    "docs/index.md",
    "docs/performance.md",
    "docs/serving.md",
    "docs/ingestion.md",
    "docs/tutorial/data_extraction_processing.md",
    ".claude/skills/verify/SKILL.md",
]
PACKAGE_ROOTS = ["models", "ops", "serving", "training", "data", "analysis", "utils", "parallel", "generation"]
SOURCES = [*(f"eventstreamgpt_tpu/{name}" for name in PACKAGE_ROOTS), "scripts", "chip_smoke.py"]


def _files(target: Path) -> list[Path]:
    if target.is_file():
        return [target]
    return sorted(p for p in target.rglob("*") if p.suffix in (".py", ".json", ".md", ".yaml"))


@pytest.mark.parametrize("target", DOCUMENTS + SOURCES)
def test_names_nothing_retired(target):
    files = _files(REPO / target)
    assert files, target
    hits = [
        f"{path.relative_to(REPO)}:{n}: {line.strip()}"
        for path in files
        for n, line in enumerate(path.read_text().splitlines(), 1)
        if RETIRED.search(line)
    ]
    assert not hits, "\n".join(hits)
