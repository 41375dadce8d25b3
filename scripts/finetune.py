"""Fine-tunes a pretrained model on a stream classification task.

Rebuild of ``/root/reference/scripts/finetune.py``: thin entry over
``eventstreamgpt_tpu.training.fine_tuning.train``.

Usage::

    python -m scripts.finetune load_from_model_dir=./exp/pretrain \
        task_df_name=in_hosp_mort optimization_config.batch_size=32
"""

from __future__ import annotations

import sys

from eventstreamgpt_tpu.training.fine_tuning import FinetuneConfig
from eventstreamgpt_tpu.training.fine_tuning import train as finetune_train
from eventstreamgpt_tpu.utils.config_tool import load_config, split_config_arg


def main(argv: list[str] | None = None):
    argv = list(sys.argv[1:] if argv is None else argv)
    yaml_fp, argv = split_config_arg(argv)
    cfg = load_config(FinetuneConfig, yaml_file=yaml_fp, overrides=argv)
    return finetune_train(cfg)


if __name__ == "__main__":
    from eventstreamgpt_tpu.utils.config_tool import configure_compile_cache

    configure_compile_cache()
    from eventstreamgpt_tpu.reliability import EXIT_PREEMPTED, Preempted

    try:
        main()
    except Preempted as e:
        # Same reschedule contract as scripts/pretrain.py (docs/reliability.md).
        print(f"Preempted cleanly at step {e.step}; exiting {EXIT_PREEMPTED} for reschedule.")
        sys.exit(EXIT_PREEMPTED)
