"""Shared CLI plumbing for the training entry scripts.

Replaces the reference's hydra stack (``training/main_async_ppo.py:15-25``)
with the in-repo YAML + dotted-override merge: the command surface is the
same (``key=value`` overrides, e.g. ``examples/run_async_ppo.sh`` ports
verbatim), plus ``--config <yaml>`` and ``--backend=tpu``.
"""

from __future__ import annotations

import sys
from typing import List, Tuple


def parse_argv(argv: List[str]) -> Tuple[dict, List[str]]:
    """Split flags (--config/--backend/--help) from key=value overrides."""
    flags = {"config": None, "backend": "tpu", "help": False}
    overrides: List[str] = []
    it = iter(argv)
    for a in it:
        if a == "--help" or a == "-h":
            flags["help"] = True
        elif a.startswith("--backend="):
            flags["backend"] = a.split("=", 1)[1]
        elif a == "--backend":
            flags["backend"] = next(it)
        elif a.startswith("--config="):
            flags["config"] = a.split("=", 1)[1]
        elif a == "--config":
            flags["config"] = next(it)
        elif "=" in a and not a.startswith("-"):
            overrides.append(a)
        else:
            raise SystemExit(f"unrecognized argument: {a!r}")
    return flags, overrides


def main(experiment_name: str, default_cls) -> None:
    from areal_tpu.api import cli_args as CA

    flags, overrides = parse_argv(sys.argv[1:])
    cfg = default_cls()
    if flags["help"]:
        CA.print_config_help(cfg)
        raise SystemExit(0)
    if flags["backend"] not in ("tpu", "jax"):
        raise SystemExit(
            f"--backend={flags['backend']} is not supported by the TPU "
            "framework (use --backend=tpu)"
        )
    if flags["config"]:
        CA.load_yaml(cfg, flags["config"])
    CA.apply_overrides(cfg, overrides)
    # "tpu" is a promise the device-owning workers keep: they refuse to
    # start on any other platform unless JAX_PLATFORMS says cpu
    # (apps/launcher._child_init). "jax" takes whatever jax finds.
    cfg.backend = flags["backend"]
    # Fail bad modes (e.g. the descoped mode=ray) at parse time, while
    # the operator is still at the command line.
    CA.validate_config(cfg)
    cfg.resolve_trial_name()

    from areal_tpu.base import logging

    logger = logging.getLogger("quickstart")
    logger.info(
        f"launching {experiment_name}: experiment_name={cfg.experiment_name} "
        f"trial_name={cfg.trial_name} allocation_mode={cfg.allocation_mode!r}"
    )

    from areal_tpu.apps.launcher import run_experiment

    result = run_experiment(cfg)
    logger.info(f"experiment finished: steps={result.get('steps')}")
