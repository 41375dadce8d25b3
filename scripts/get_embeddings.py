"""Extracts pooled per-subject embeddings from a pretrained model.

Rebuild of ``/root/reference/scripts/get_embeddings.py``: thin entry over
``eventstreamgpt_tpu.training.embedding.get_embeddings``.

Usage::

    python -m scripts.get_embeddings load_from_model_dir=./exp/pretrain \
        task_df_name=in_hosp_mort
"""

from __future__ import annotations

import sys

from eventstreamgpt_tpu.training.embedding import get_embeddings
from eventstreamgpt_tpu.training.fine_tuning import FinetuneConfig
from eventstreamgpt_tpu.utils.config_tool import load_config, split_config_arg


def main(argv: list[str] | None = None):
    argv = list(sys.argv[1:] if argv is None else argv)
    yaml_fp, argv = split_config_arg(argv)
    cfg = load_config(FinetuneConfig, yaml_file=yaml_fp, overrides=argv)
    return get_embeddings(cfg)


if __name__ == "__main__":
    from eventstreamgpt_tpu.utils.config_tool import configure_compile_cache

    configure_compile_cache()
    main()
