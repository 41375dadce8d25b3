"""Zero-shot evaluation via generation with a user task labeler.

Rebuild of ``/root/reference/scripts/zeroshot.py``: thin entry over
``eventstreamgpt_tpu.training.zero_shot_evaluator.zero_shot_evaluation``.

Usage::

    python -m scripts.zeroshot load_from_model_dir=./exp/pretrain \
        task_df_name=in_hosp_mort task_specific_params.num_samples=8
"""

from __future__ import annotations

import sys

from eventstreamgpt_tpu.training.fine_tuning import FinetuneConfig
from eventstreamgpt_tpu.training.zero_shot_evaluator import zero_shot_evaluation
from eventstreamgpt_tpu.utils.config_tool import load_config, split_config_arg


def main(argv: list[str] | None = None):
    argv = list(sys.argv[1:] if argv is None else argv)
    yaml_fp, argv = split_config_arg(argv)
    cfg = load_config(FinetuneConfig, yaml_file=yaml_fp, overrides=argv)
    return zero_shot_evaluation(cfg)


if __name__ == "__main__":
    from eventstreamgpt_tpu.utils.config_tool import configure_compile_cache

    configure_compile_cache()
    main()
