"""The check's control and its planted faults, as patches of a problem.

Each patch replaces methods of one problem instance, through the public
``FixedPointProblem`` / ``DeviceBlockPlan`` contract, so the window's
workers run the broken path and the check, which drives the same
instance, sees it too:

* ``float32`` (the control): the family's plain reference computed in
  float32 takes the program's place in every block update and residual:
  the precision step below the configuration's float64; where the mix
  accelerates, ``chipbench/anderson.py``'s reference in float32 also
  takes the place of the program's Anderson state in every fire;
* ``unchanged``: a block update returns its block as it was;
* ``half``: a block update leaves the second half of its block as it was;
* ``no_exchange``: a block update reads zeros for every value outside its
  block (the halo rows);
* ``altered``: a block update scales its largest value by 1 + 1e-6.

A patch returns the factory of the Anderson state (``AndersonConfig`` ->
state) that the window's coordinators build in the program's place
(``harness.anderson_kept``), or None.  ``COMBINE_KINDS`` break that state
alone, for mixes with ``accel``:

* ``dropped_newest``: the program's state with each (x, g, f) pushed one
  push late, so its solve and combine leave out the newest column.
"""

from __future__ import annotations

import dataclasses

import numpy as np

KINDS = ("float32", "unchanged", "half", "no_exchange", "altered")
COMBINE_KINDS = ("dropped_newest",)


def _break(kind: str, new: np.ndarray, old: np.ndarray) -> np.ndarray:
    """``new`` block values as the fault ``kind`` returns them."""
    out = np.array(new, dtype=np.float64)
    if kind == "unchanged":
        return np.array(old, dtype=np.float64)
    if kind == "half":
        out[out.size // 2:] = old[out.size // 2:]
    elif kind == "altered":
        i = int(np.argmax(np.abs(out)))
        out[i] *= 1.0 + 1e-6
    return out


class _RefPlan:
    """A device plan whose step is the float32 reference on the whole
    iterate."""

    def __init__(self, ref, indices, n: int):
        self.ref, self.indices, self.needs = ref, indices, [slice(0, n)]

    def refresh(self, block_values) -> None:
        pass

    def step(self, x):
        new = self.ref.block_step(x, self.indices).astype(np.float64)
        return new, 0.0


class _BrokenPlan:
    """The program's device plan with ``kind`` applied to each step."""

    def __init__(self, plan, kind: str):
        self.plan, self.kind, self.needs = plan, kind, plan.needs
        self.block = None  # what the resident block holds, on the host

    def refresh(self, block_values) -> None:
        self.block = np.array(block_values, dtype=np.float64)
        self.plan.refresh(block_values)

    def step(self, *need_vals):
        if self.kind == "unchanged":
            return self.block.copy(), 0.0
        if self.kind == "no_exchange":
            need_vals = [np.zeros_like(v) for v in need_vals]
        new, norm = self.plan.step(*need_vals)
        self.block = _break(self.kind, new, self.block)
        if self.kind != "no_exchange":
            self.plan.refresh(self.block)
        return self.block.copy(), norm


class _DroppedNewest:
    """The program's Anderson state, each triple pushed one push late
    (held as a copy, since the coordinator's iterate changes after)."""

    def __init__(self, state):
        self.state, self.held = state, None

    def push(self, x, g, f) -> None:
        if self.held is not None:
            self.state.push(*self.held)
        self.held = tuple(np.array(v, np.float64) for v in (x, g, f))

    def __getattr__(self, name):
        return getattr(self.state, name)


def patch(kind: str, cell, seed: int):
    """A function that breaks a problem of ``cell`` in place and returns
    the factory of the window's Anderson state, or None."""
    if kind not in KINDS + COMBINE_KINDS:
        raise ValueError(f"unknown fault {kind!r}; known: "
                         f"{KINDS + COMBINE_KINDS}")
    accel = "accel" in cell.mix
    if kind in COMBINE_KINDS and not accel:
        raise ValueError(f"{kind!r} needs a mix with accel; {cell.name} "
                         "has none")

    def apply(problem):
        if kind == "dropped_newest":
            from repro.core import AndersonState

            return lambda config: _DroppedNewest(AndersonState(config))
        plan_of, update = problem.device_block_plan, problem.block_update
        if kind == "float32":
            from chipbench import anderson

            ref = cell.family.Reference(cell.config, seed, np.float32)
            problem.block_update = lambda x, idx: ref.block_step(
                x, idx).astype(np.float64)
            problem.residual_norm = ref.residual_norm
            problem.device_block_plan = lambda idx, mode: (
                None if plan_of(idx, mode) is None
                else _RefPlan(ref, idx, problem.n))
            return (lambda config: anderson.State(
                dataclasses.asdict(config), np.float32)) if accel else None

        def block_update(x, idx):
            if kind == "no_exchange":
                mine = np.zeros_like(x)
                mine[idx] = x[idx]
                return update(mine, idx)
            return _break(kind, update(x, idx), x[idx])

        def device_block_plan(idx, mode):
            plan = plan_of(idx, mode)
            return None if plan is None else _BrokenPlan(plan, kind)

        problem.block_update = block_update
        problem.device_block_plan = device_block_plan
        return None

    return apply
