"""Plan → JAX placement: map each task's (dp, pp, tp) + tasklet assignment
onto the host's real devices.

The scheduler plans against the paper's heterogeneous pools (up to 64
GPUs); the host executing the plan usually has a different device count.
Folding is *group-aware* and deterministic, so the same plan always lands
on the same submeshes:

  1. collect the distinct plan device ids used by the whole plan, sorted
     ascending.  When they fit on the host (``#ids <= L`` real devices)
     the i-th smallest plan id folds onto ``local_devices[i]`` — an
     *injective* map, so disjoint plan groups land on disjoint real
     device sets and the executor's disjoint-concurrent lanes genuinely
     overlap.  (A plan over ids 0..L-1 folds to the identity map.)
  2. only when the host is oversubscribed (``#ids > L``) does folding
     fall back to ``d % L``; distinct groups can then collide on a real
     device.  Every such collision is recorded in
     ``DeviceFolding.collisions`` so the engine can tag the affected
     events instead of reporting fake concurrency.
  3. per task, duplicates collapse (first plan-order occurrence wins),
     giving ``n`` distinct real devices; the task's mesh is
     ``(data=n/tp', model=tp')`` with ``tp' = gcd(tp, n)`` — tensor
     parallelism survives when it divides the folded device count,
     pipeline stages collapse into the data axis (no cross-host pipeline
     runtime inside one process).

Mesh axes are ``("data", "model")`` so ``parallel.sharding`` param rules
apply unchanged.  ``TaskPlacement`` carries everything the execution path
needs to shard for real: the mesh, ``param_shardings`` /
``batch_shardings`` built from ``parallel.sharding``, the realized
``tp_eff``, and ``rep_plan_devices`` (one representative plan id per
real device) from which ``Engine.realized_plan`` rebuilds a plan the
cost model can price at the *realized* parallelization.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import numpy as np

from repro.core.plan import Plan
from repro.parallel import sharding as sh


@dataclasses.dataclass(frozen=True)
class DeviceFolding:
    """Plan-wide folding decision: plan device id -> local device index.

    ``collisions`` lists real devices shared by *distinct* plan groups
    (only possible when ``oversubscribed``): tuples of
    ``(local_index, group_indices)``.  Within-group many-to-one folding
    is not a collision — it shrinks dp/tp, which placement accounts for.
    """
    mapping: Dict[int, int]
    oversubscribed: bool
    collisions: Tuple[Tuple[int, Tuple[int, ...]], ...]
    colliding_groups: frozenset

    @property
    def n_collisions(self) -> int:
        return len(self.collisions)


@dataclasses.dataclass
class TaskPlacement:
    task: int
    dp: int                       # plan-level parallelization
    pp: int
    tp: int
    plan_devices: Tuple[int, ...]   # plan device ids, tasklet order
    local_devices: Tuple            # distinct folded jax devices
    mesh: Mesh                      # ("data", "model") over local_devices
    rep_plan_devices: Tuple[int, ...] = ()  # plan id per distinct device
    collision: bool = False       # group shares a real device with another
    # real devices the whole plan spans (the engine sets it with the
    # plan's placements)
    plan_n_devices: int = dataclasses.field(default=1, init=False)

    @property
    def mesh_shape(self) -> Tuple[int, int]:
        return tuple(self.mesh.devices.shape)

    @property
    def n_devices(self) -> int:
        return len(self.local_devices)

    @property
    def tp_eff(self) -> int:
        """Realized tensor parallelism after folding (mesh model dim)."""
        return self.mesh.devices.shape[1]

    @property
    def dp_eff(self) -> int:
        """Realized data parallelism after folding (mesh data dim)."""
        return self.mesh.devices.shape[0]

    @property
    def sharded(self) -> bool:
        """True when this task actually spans several real devices."""
        return len(self.local_devices) > 1

    def activation_rules(self):
        """Activation-sharding rules for this placement's (dp, tp).

        Sequence sharding stays off: RL batches are short and ragged, and
        the decode path keeps B on ``data`` only."""
        return sh.default_activation_rules(seq_shard=False)

    def param_shardings(self, params):
        """NamedShardings for a parameter pytree under this placement
        (specs sanitized so non-dividing axes drop to replication)."""
        specs = sh.param_tree_specs(params)
        return sh.named_shardings(self.mesh, specs, params)

    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_shardings(self, tree):
        """Leading-dim ``data`` sharding for a batch pytree (replicated
        when the batch dim does not divide the data axis)."""
        return jax.tree_util.tree_map(
            lambda x: NamedSharding(
                self.mesh,
                sh.sanitize_spec(P("data"), np.shape(x), self.mesh)),
            tree)

    def shard_batch(self, tree):
        """Commit a batch pytree onto this mesh, split over ``data``."""
        return jax.device_put(tree, self.batch_shardings(tree))

    def put(self, tree):
        """Place a task's inputs for its program: split over ``data`` on
        a mesh of several devices; moved onto the task's one device when
        the plan spans others (the inputs may be committed to another
        group's devices); left as they are on a one-device plan."""
        return self._place(tree, self.batch_shardings)

    def put_params(self, params):
        """Place a parameter tree that another task owns (and updates)
        for this task's program, as ``put`` places inputs: onto this
        placement's parameter shardings on a mesh."""
        return self._place(params, self.param_shardings)

    def _place(self, tree, shardings):
        if self.sharded:
            return jax.device_put(tree, shardings(tree))
        if self.plan_n_devices > 1:
            return jax.device_put(tree, self.local_devices[0])
        return tree


def fold_plan(plan: Plan, local_devices) -> DeviceFolding:
    """Group-aware folding for a whole plan.

    Injective (rank-ordered) when every distinct plan device id fits on
    the host; ``d % L`` with a collision report otherwise."""
    L = len(local_devices)
    ids = sorted({int(d) for g in plan.groups for d in g.devices}
                 | {int(d) for a in plan.assignment.values()
                    for d in np.asarray(a).reshape(-1)})
    oversubscribed = len(ids) > L
    if oversubscribed:
        mapping = {d: d % L for d in ids}
    else:
        mapping = {d: i for i, d in enumerate(ids)}

    # collision report: real devices claimed by >= 2 distinct groups
    claims: Dict[int, set] = {}
    for gi, g in enumerate(plan.groups):
        for d in g.devices:
            if int(d) in mapping:
                claims.setdefault(mapping[int(d)], set()).add(gi)
    collisions = tuple(
        (li, tuple(sorted(gs))) for li, gs in sorted(claims.items())
        if len(gs) > 1)
    colliding = frozenset(g for _, gs in collisions for g in gs)
    return DeviceFolding(mapping, oversubscribed, collisions, colliding)


def fold_devices(plan_devices: Sequence[int], local_devices,
                 mapping: Optional[Dict[int, int]] = None) -> List:
    """Fold one task's plan device ids onto distinct local devices.

    With ``mapping`` (from :func:`fold_plan`) the plan-wide group-aware
    rule applies; without it, the legacy ``d % L`` rule — kept for
    callers folding a device list with no plan in hand."""
    L = len(local_devices)
    if mapping is None:
        folded = [local_devices[int(d) % L] for d in plan_devices]
    else:
        folded = [local_devices[mapping[int(d)]] for d in plan_devices]
    distinct, seen = [], set()
    for dev in folded:
        if id(dev) not in seen:
            seen.add(id(dev))
            distinct.append(dev)
    return distinct


def build_placement(plan: Plan, t: int,
                    devices: Optional[Sequence] = None,
                    folding: Optional[DeviceFolding] = None
                    ) -> TaskPlacement:
    devices = list(devices) if devices is not None else jax.devices()
    if folding is None:
        folding = fold_plan(plan, devices)
    dp, pp, tp = plan.parallel[t]
    plan_devs = tuple(int(d) for d in plan.assignment[t].reshape(-1))
    distinct = fold_devices(plan_devs, devices, folding.mapping)
    # representative plan id per distinct real device (first claimant)
    reps, seen = [], set()
    for d in plan_devs:
        dev = devices[folding.mapping[int(d)]]
        if id(dev) not in seen:
            seen.add(id(dev))
            reps.append(int(d))
    n = len(distinct)
    tp_eff = math.gcd(tp, n)
    mesh = Mesh(np.array(distinct).reshape(n // tp_eff, tp_eff),
                ("data", "model"))
    group = plan.group_of(t)
    gi = plan.groups.index(group) if group in plan.groups else -1
    return TaskPlacement(t, dp, pp, tp, plan_devs, tuple(distinct), mesh,
                         rep_plan_devices=tuple(reps),
                         collision=gi in folding.colliding_groups)


def build_placements(plan: Plan, tasks: Sequence[int],
                     devices: Optional[Sequence] = None
                     ) -> Dict[int, TaskPlacement]:
    devices = list(devices) if devices is not None else jax.devices()
    folding = fold_plan(plan, devices)
    return {t: build_placement(plan, t, devices, folding) for t in tasks}
