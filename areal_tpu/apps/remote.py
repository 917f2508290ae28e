"""Per-node worker entrypoint for cluster schedulers.

Parity target: ``realhf/apps/remote.py:54`` (main_worker) — a scheduler
(slurm, or any launcher that can run a command on a node) starts ONE process
per worker via this module; the process reconstructs the experiment config
from the dumped ``config.yaml``, then runs exactly one worker role. Worker
discovery happens through name_resolve exactly as in local mode, so the
system fabric is identical — only process placement changes.

Usage (what the slurm scripts generate):

    python -m areal_tpu.apps.remote --experiment-cls async-ppo-math \
        --config <run>/config.yaml --role trainer --rank $SLURM_PROCID \
        --world $SLURM_NTASKS
"""

from __future__ import annotations

import argparse
import os
from typing import List, Optional

from areal_tpu.base import logging

logger = logging.getLogger("apps.remote")

ROLES = ("master", "trainer", "gen_fleet", "rollout")


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return int(v) if v not in (None, "") else default


def build_config(experiment_cls: str, config_path: str):
    import areal_tpu.experiments  # noqa: F401 — populates the registry
    from areal_tpu.api import cli_args as CA
    from areal_tpu.experiments import make_experiment_cls

    cfg = make_experiment_cls(experiment_cls)()
    CA.load_yaml(cfg, config_path)
    cfg.resolve_trial_name()
    return cfg


def run_role(
    exp_cfg,
    role: str,
    rank: int = 0,
    world: int = 1,
    index: int = 0,
) -> None:
    """Run one worker role to completion (the scheduler owns the process).
    The platform follows ``JAX_PLATFORMS`` (apps/launcher.py)."""
    from areal_tpu.apps import launcher as L

    setup = exp_cfg.initial_setup()
    if role == "master":
        L._child_init(exp_cfg, False)
        from areal_tpu.system.master_worker import MasterWorker

        MasterWorker(setup["master"], setup["dfg"]).run()
    elif role == "trainer":
        tc = setup["trainer"]
        tc.dist_rank = rank
        tc.dist_world = world
        L.trainer_entry(exp_cfg, tc)
    elif role == "gen_fleet":
        if "gen_servers" not in setup:
            raise SystemExit("experiment has no generation fleet (sync mode)")
        L.gen_fleet_entry(
            exp_cfg, setup["gen_servers"], setup["gserver_manager"]
        )
    elif role == "rollout":
        rcs = setup.get("rollout_workers", [])
        if not 0 <= index < len(rcs):
            raise SystemExit(
                f"rollout index {index} out of range (have {len(rcs)})"
            )
        L.rollout_entry(exp_cfg, rcs[index])
    else:
        raise SystemExit(f"unknown role {role!r}; have {ROLES}")


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--experiment-cls", required=True,
                    help="registered experiment name (experiments registry)")
    ap.add_argument("--config", required=True, help="path to config.yaml")
    ap.add_argument("--role", required=True, choices=ROLES)
    ap.add_argument("--rank", type=int,
                    default=_env_int("SLURM_PROCID", 0))
    ap.add_argument("--world", type=int,
                    default=_env_int("SLURM_NTASKS", 1))
    ap.add_argument("--index", type=int,
                    default=_env_int("SLURM_PROCID", 0),
                    help="worker index within the role group (rollout); "
                         "defaults to SLURM_PROCID inside srun tasks")
    ap.add_argument("--force-cpu", action="store_true",
                    help="same as exporting JAX_PLATFORMS=cpu")
    args = ap.parse_args(argv)
    if args.force_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    cfg = build_config(args.experiment_cls, args.config)
    logger.info(
        f"remote worker: role={args.role} rank={args.rank}/{args.world} "
        f"index={args.index} experiment={cfg.experiment_name}/"
        f"{cfg.trial_name}"
    )
    run_role(cfg, args.role, rank=args.rank, world=args.world,
             index=args.index)


if __name__ == "__main__":
    main()
