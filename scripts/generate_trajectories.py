"""Generates future-trajectory samples and writes them as parquet.

Rebuild of ``/root/reference/scripts/generate_trajectories.py``: thin entry
over ``eventstreamgpt_tpu.evaluation.generate_trajectories``.

Usage::

    python -m scripts.generate_trajectories load_from_model_dir=./exp/pretrain \
        task_specific_params.num_samples=4 task_specific_params.max_new_events=32
"""

from __future__ import annotations

import sys

from eventstreamgpt_tpu.evaluation import GenerateConfig, generate_trajectories
from eventstreamgpt_tpu.utils.config_tool import load_config, split_config_arg


def main(argv: list[str] | None = None):
    argv = list(sys.argv[1:] if argv is None else argv)
    yaml_fp, argv = split_config_arg(argv)
    cfg = load_config(GenerateConfig, yaml_file=yaml_fp, overrides=argv)
    return generate_trajectories(cfg)


if __name__ == "__main__":
    from eventstreamgpt_tpu.utils.config_tool import configure_compile_cache

    configure_compile_cache()
    main()
