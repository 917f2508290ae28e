"""Device meshes and the allocation-mode vocabulary.

Replaces the reference's ``ProcessTopology``/``ParallelGrid``
(``realhf/base/topology.py:86,369``) and the ``AllocationMode`` parser
(``realhf/experiments/common/utils.py:245-375``). On TPU there are no NCCL
process groups to build — a ``jax.sharding.Mesh`` plus named axes subsumes
them; GSPMD inserts the collectives.

Axis convention (order fixed so ICI-neighbour axes get the innermost dims):

    ("dp", "fsdp", "ep", "pp", "sp", "tp")

 - ``dp``    pure data parallel (params replicated)
 - ``fsdp``  data parallel with params/opt-state sharded (ZeRO-3 style)
 - ``ep``    expert parallel: a slice of the data dimension whose shards
             own disjoint experts (models/moe.py gathers tokens over it)
 - ``pp``    pipeline stages over the stacked-layer axis
 - ``sp``    sequence/context parallel (ring attention over this axis)
 - ``tp``    tensor parallel (heads / ffn sharded)

Parallelism of one model role is a ``ParallelSpec``; an experiment-wide
``AllocationMode`` string assigns specs per role, with a TPU vocabulary:

    "d2t4"                      → dp=2, tp=4 (one global spec)
    "d2f2s2t2"                  → dp=2, fsdp=2, sp=2, tp=2
    "gen.d4t2+train.f8t2"       → decoupled generation vs trainer slices
    "actor_gen:d4t2,actor_train:f4t4"  → per-MFC specs
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_ORDER = ("dp", "fsdp", "ep", "pp", "sp", "tp")
# Short letter used in allocation strings per axis.
_AXIS_LETTER = {"d": "dp", "f": "fsdp", "p": "pp", "s": "sp", "t": "tp", "e": "ep"}


@dataclasses.dataclass(frozen=True)
class ParallelSpec:
    """Degrees along each mesh axis for one model role.

    ``ep`` (expert parallel) is a REAL mesh axis: the batch dim shards over
    it like dp/fsdp (DATA_AXES), expert weights shard their expert axis
    over it (sharding.py), and models/moe.py gathers a micro-batch's tokens
    over it so that each shard computes its own experts' rows. Validated
    against the model's expert count at parse time
    (api/cli_args.validate_config).
    """

    dp: int = 1
    fsdp: int = 1
    pp: int = 1
    sp: int = 1
    tp: int = 1
    ep: int = 1

    @property
    def world_size(self) -> int:
        return self.dp * self.fsdp * self.ep * self.pp * self.sp * self.tp

    @property
    def data_degree(self) -> int:
        """Number of distinct data shards (dp × fsdp × ep)."""
        return self.dp * self.fsdp * self.ep

    def mesh_shape(self) -> Tuple[int, ...]:
        return (self.dp, self.fsdp, self.ep, self.pp, self.sp, self.tp)

    @classmethod
    def parse(cls, s: str) -> "ParallelSpec":
        """Parse e.g. "d2f2s1t4" / "d2m2p1" (reference letters: m=tp, p=pp)."""
        s = s.strip().lower()
        if not re.fullmatch(r"(?:[a-z]\d+)+", s):
            raise ValueError(f"malformed parallel spec '{s}'")
        out: Dict[str, int] = {}
        for letter, num in re.findall(r"([a-z])(\d+)", s):
            if letter == "m":  # reference spelling for tensor(model)-parallel
                axis = "tp"
            else:
                axis = _AXIS_LETTER.get(letter)
            if axis is None:
                raise ValueError(f"unknown axis letter '{letter}' in '{s}'")
            if axis in out:
                raise ValueError(f"duplicate axis '{letter}' in '{s}'")
            out[axis] = int(num)
        if not out:
            raise ValueError(f"cannot parse parallel spec '{s}'")
        return cls(**out)

    def __str__(self) -> str:
        return "".join(
            f"{l}{getattr(self, a)}"
            for l, a in (
                ("d", "dp"), ("f", "fsdp"), ("p", "pp"), ("s", "sp"),
                ("t", "tp"), ("e", "ep"),
            )
            if getattr(self, a) != 1
        ) or "d1"


def make_mesh(
    spec: ParallelSpec,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh with the canonical axis order.

    Axis order puts ``tp`` innermost so tensor-parallel collectives ride
    nearest-neighbour ICI links; ``dp``/``fsdp`` outermost so gradient
    reductions use the remaining (possibly DCN) links — the standard layout
    from the scaling-book recipe.
    """
    if devices is None:
        devices = jax.devices()
    n = spec.world_size
    if len(devices) < n:
        raise ValueError(f"spec {spec} needs {n} devices, have {len(devices)}")
    arr = np.asarray(devices[:n]).reshape(spec.mesh_shape())
    return Mesh(arr, AXIS_ORDER)


# Composite axis names used in PartitionSpecs (sharding.py): the batch dim
# shards over every DP flavour — ep included, since expert parallelism is
# a slice of the data dimension (tokens arrive ep-partitioned and the MoE
# layer gathers them over ep for each shard's experts).
DATA_AXES = ("dp", "fsdp", "ep")


@dataclasses.dataclass(frozen=True)
class AllocationMode:
    """Experiment-wide device allocation (reference utils.py:245-375).

    ``global_spec`` — one spec for every MFC (colocated);
    ``gen_spec`` — when decoupled, the generation fleet's spec;
    ``per_mfc`` — optional per-MFC overrides.
    """

    global_spec: ParallelSpec
    gen_spec: Optional[ParallelSpec] = None
    per_mfc: Dict[str, ParallelSpec] = dataclasses.field(default_factory=dict)

    @property
    def decoupled(self) -> bool:
        return self.gen_spec is not None

    @classmethod
    def parse(cls, s: str) -> "AllocationMode":
        s = s.strip()
        if ":" in s:  # per-MFC: "actor_gen:d4t2,actor_train:f4t4"
            per = {}
            for part in s.split(","):
                name, sep, spec = part.partition(":")
                if not sep or not name.strip() or not spec.strip():
                    raise ValueError(
                        f"malformed per-MFC allocation entry '{part}' in '{s}'"
                    )
                name = name.strip()
                if name in per:
                    raise ValueError(
                        f"duplicate MFC '{name}' in allocation mode '{s}'"
                    )
                per[name] = ParallelSpec.parse(spec)
            train = per.get("actor_train") or next(iter(per.values()))
            gen = per.get("actor_gen")
            return cls(global_spec=train, gen_spec=gen, per_mfc=per)
        if "+" in s:  # decoupled: "gen.d4t2+train.f8t2" or "sglang.d4+d2t2"
            gen_part, train_part = s.split("+")
            gen_part = gen_part.split(".")[-1]
            train_part = train_part.split(".")[-1]
            return cls(
                global_spec=ParallelSpec.parse(train_part),
                gen_spec=ParallelSpec.parse(gen_part),
            )
        return cls(global_spec=ParallelSpec.parse(s))
