"""Pretrains a generative event-stream transformer.

Rebuild of ``/root/reference/scripts/pretrain.py``: a thin entry point over
``eventstreamgpt_tpu.training.pretrain.train`` with hydra-style
``key.sub=value`` overrides (``utils.config_tool``). An optional
``--config <yaml>`` supplies base values.

Usage::

    python -m scripts.pretrain data_config.save_dir=./processed/sample \
        optimization_config.batch_size=32 save_dir=./exp/pretrain
"""

from __future__ import annotations

import sys
from pathlib import Path

import yaml

from eventstreamgpt_tpu.training import PretrainConfig
from eventstreamgpt_tpu.training import train as pretrain_train
from eventstreamgpt_tpu.utils.config_tool import load_config, split_config_arg


def main(argv: list[str] | None = None):
    argv = list(sys.argv[1:] if argv is None else argv)
    yaml_fp, argv = split_config_arg(argv)

    cfg = load_config(PretrainConfig, yaml_file=yaml_fp, overrides=argv)

    # Dump the resolved config next to the run (reference pretrain.py:34-41).
    save_dir = Path(cfg.save_dir)
    save_dir.mkdir(parents=True, exist_ok=True)
    import json

    from eventstreamgpt_tpu.utils.config_tool import unstructure

    with open(save_dir / "pretrain_config.yaml", "w") as f:
        # json round-trip coerces non-YAML-native leaves (Paths, enums) to str.
        yaml.safe_dump(json.loads(json.dumps(unstructure(cfg), default=str)), f)

    return pretrain_train(cfg)


if __name__ == "__main__":
    from eventstreamgpt_tpu.utils.config_tool import configure_compile_cache

    configure_compile_cache()
    from eventstreamgpt_tpu.reliability import EXIT_PREEMPTED, Preempted

    try:
        main()
    except Preempted as e:
        # The orchestrator contract (docs/reliability.md): a graceful
        # SIGTERM/SIGINT drain wrote a final mid-epoch checkpoint; exit with
        # the distinct "reschedule me" status instead of a failure code.
        print(f"Preempted cleanly at step {e.step}; exiting {EXIT_PREEMPTED} for reschedule.")
        sys.exit(EXIT_PREEMPTED)
