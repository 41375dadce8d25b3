"""Test package. Shared locations of the committed sample dataset (the repo's
own ``sample_data/``, regenerable with ``python -m scripts.make_sample_data``):
every test that needs a real processed dataset reads it from here, never from
outside the checkout."""

from pathlib import Path

SAMPLE_ROOT = Path(__file__).resolve().parents[1] / "sample_data"
SAMPLE_RAW_DIR = SAMPLE_ROOT / "raw"
SAMPLE_DIR = SAMPLE_ROOT / "processed" / "sample"
