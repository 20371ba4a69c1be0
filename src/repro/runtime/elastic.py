"""Elastic scaling: re-mesh and re-lower when hosts join/leave.

SharedDB's always-on plan is compiled for a fixed mesh; elasticity is
handled at CYCLE boundaries (never inside a step):

  1. failure/resize detected (heartbeats, scheduler event);
  2. drain: finish the in-flight cycle, checkpoint (atomic);
  3. pick the largest supported mesh <= surviving chips from the ladder;
  4. re-lower the same step functions under the new mesh (pure function of
     config x mesh — this is exactly what launch/dryrun.py proves compiles
     for every (arch x shape x mesh));
  5. restore the checkpoint re-sharded (per-host shards re-read by the new
     owners) and resume at the saved step.

The drain -> re-lower -> resume recipe is shared machinery: the same
skeleton drives plan FOLDING (core/folding.py), where the re-lower happens
in the BACKGROUND while the old compiled heartbeat keeps serving, and the
drain/swap collapses to a single beat boundary.  ``relower_recipe``
produces both variants.

The mesh ladder keeps axis shapes divisor-friendly so every config in
repro.configs stays shardable after shrink.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax


# (pods, data, model) ladder — model axis kept at 16 so TP-sharded configs
# stay valid; shrink sheds data-parallel rows first (batch divisibility is
# re-checked against the config at selection time).
DEFAULT_LADDER: List[Tuple[int, ...]] = [
    (2, 16, 16), (1, 16, 16), (1, 8, 16), (1, 4, 16), (1, 2, 16),
    (1, 1, 16), (1, 1, 8), (1, 1, 4), (1, 1, 2), (1, 1, 1),
]


def relower_recipe(current, target, *, what: str = "step functions",
                   background: bool = False) -> dict:
    """The drain -> re-lower -> resume recipe as structured data.

    ``background=False`` is the elastic-shrink variant (stop-the-world at a
    cycle boundary: drain, checkpoint, re-lower, restore).  ``background=
    True`` is the plan-folding variant: the re-lower overlaps serving and
    only the swap itself lands at a beat boundary, so already-admitted
    clients keep their 2-cycle latency bound throughout.
    """
    if background:
        steps = [
            f"re-lower {what} under {target} in the background "
            "(old compiled heartbeat keeps serving)",
            "drain in-flight beats at the next beat boundary",
            "migrate carries into the new layout (atomic swap)",
            "resume: first post-swap beat is a full-rescan reseed",
        ]
    else:
        steps = [
            "drain in-flight cycle",
            "checkpoint (atomic commit)",
            f"re-lower {what} under mesh {target}",
            "restore re-sharded checkpoint",
            "resume at saved step",
        ]
    return {"current": current, "target": target, "steps": steps}


@dataclasses.dataclass
class ElasticMeshManager:
    ladder: List[Tuple[int, ...]] = dataclasses.field(
        default_factory=lambda: list(DEFAULT_LADDER))

    def __post_init__(self):
        # ``select`` returns the FIRST rung that fits, which is only the
        # LARGEST rung when the ladder is sorted descending by chip count.
        # A hand-built unsorted ladder used to silently under-provision
        # (e.g. [(1,1,1), (1,2,2)] always selected the 1-chip rung) —
        # validate the rungs and normalize the order at construction.
        for shape in self.ladder:
            if len(shape) != 3 or any(
                    not isinstance(d, int) or d < 1 for d in shape):
                raise ValueError(
                    f"ladder rung {shape!r} is not a (pods, data, model) "
                    "tuple of positive ints")
        self.ladder = sorted(self.ladder,
                             key=lambda s: s[0] * s[1] * s[2],
                             reverse=True)

    def select(self, chips_alive: int,
               global_batch: Optional[int] = None) -> Tuple[int, ...]:
        """Largest rung that fits the surviving chips (and batch)."""
        for shape in self.ladder:
            n = shape[0] * shape[1] * shape[2]
            if n > chips_alive:
                continue
            if global_batch is not None:
                dp = shape[0] * shape[1]
                if global_batch % dp != 0:
                    continue
            return shape
        raise RuntimeError(f"no viable mesh for {chips_alive} chips")

    def make_mesh(self, shape: Tuple[int, ...],
                  devices: Optional[Sequence] = None):
        """Build the mesh, optionally restricted to an ALIVE device list.

        ``jax.devices()[:n]`` is only correct when the failure happened at
        the tail of the device list; after a mid-list failure the dead
        device is still enumerated and would be meshed in.  Callers that
        learned of a death (heartbeats) pass the surviving devices
        explicitly.
        """
        n = shape[0] * shape[1] * shape[2]
        pool = list(devices) if devices is not None else jax.devices()
        if len(pool) < n:
            raise RuntimeError(
                f"mesh shape {shape} needs {n} devices, only "
                f"{len(pool)} alive")
        pool = pool[:n]
        from repro.core.sharding import make_mesh
        if shape[0] > 1:
            return make_mesh(shape, ("pod", "data", "model"), pool)
        return make_mesh(shape[1:], ("data", "model"), pool)

    def shrink_plan(self, current: Tuple[int, ...], chips_alive: int,
                    global_batch: Optional[int] = None) -> dict:
        """The drain -> re-mesh -> restore recipe as structured data."""
        target = self.select(chips_alive, global_batch)
        return relower_recipe(current, target, what="step")
