"""What the output check must fail: the control and the planted faults.

Each is a :class:`cb_program.Program` that the harness drives in the
program's place, through the same set-up, window and check as a run:

- ``control``: the reference planner in float32, one precision below the
  float64 the configurations state, put in the planner's place (the step a
  later change could be tempted to take);
- ``altered_answer``: the program, with one answer altered where it is
  produced: in every ``plan_many`` result the smallest beta of one plan
  halved (the plan no longer keeps the MDS property), and one byte flipped
  in every GF(2^8) product;
- ``half_batch``: the planner plans the first half of each batch and
  repeats it over the rest, and the kernel computes the first half of the
  rows of each product and leaves the rest 0;
- ``unchanged_state``: repairs return with the store unchanged.

The one-chip cells have no exchange between chips to leave out.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import cb_planref
from cb_program import Program


def _result(res, scheme, times, traffic, betas, parents, lower_bounds,
            engine):
    return dataclasses.replace(res, scheme=scheme, times=times,
                               traffic=traffic, betas=betas, parents=parents,
                               lower_bounds=lower_bounds, engine=engine)


class Control(Program):
    """The reference planner in float32 where the jit planner was."""

    def __init__(self, config):
        super().__init__(config)
        self.ref = cb_planref.Planner(config["k"], config["d"],
                                      float(config["M"]), np.float32)

    def plan(self, caps, scheme):
        from repro.core.batched import BatchPlanResult
        r = cb_planref.plan_batch(self.ref, scheme, caps)
        return BatchPlanResult(scheme, r["times"], r["traffic"], r["betas"],
                               r["parents"], lower_bounds=r["lower_bounds"],
                               engine="control-float32")


class AlteredAnswer(Program):
    def __init__(self, config):
        super().__init__(config)
        self.rng = np.random.default_rng(0)

    def plan(self, caps, scheme):
        res = super().plan(caps, scheme)
        betas = np.array(res.betas)
        lane = self.rng.integers(betas.shape[0])
        betas[lane, np.argmin(betas[lane])] *= 0.5
        return _result(res, scheme, res.times, res.traffic, betas,
                       res.parents, res.lower_bounds, res.engine)

    def kernel_matmul(self, a, b):
        out = np.array(super().kernel_matmul(a, b))
        i = self.rng.integers(out.shape[0])
        j = self.rng.integers(out.shape[1])
        out[i, j] ^= 0x5A
        return out


class HalfBatch(Program):
    def plan(self, caps, scheme):
        B = caps.shape[0]
        half = super().plan(caps[:(B + 1) // 2], scheme)
        take = np.arange(B) % half.times.shape[0]
        lb = None if half.lower_bounds is None else half.lower_bounds[take]
        return _result(half, scheme, half.times[take], half.traffic[take],
                       half.betas[take], half.parents[take], lb, half.engine)

    def kernel_matmul(self, a, b):
        out = np.zeros((a.shape[0], b.shape[1]), np.uint8)
        half = (a.shape[0] + 1) // 2
        out[:half] = super().kernel_matmul(a[:half], b)
        return out


class UnchangedState(Program):
    def store(self, file, seed, matmul):
        sim = super().store(file, seed, matmul)
        sim.execute_plan = lambda plan, failed, provider_ids: None
        return sim


PROGRAMS = {"control": Control, "altered_answer": AlteredAnswer,
            "half_batch": HalfBatch, "unchanged_state": UnchangedState}
