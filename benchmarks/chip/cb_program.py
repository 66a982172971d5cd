"""The system under test, reached only through its public entry points.

Everything the benchmark asks of the program goes through this one class,
so the output check and its tests can put something else in its place: the
reference in a lower precision (the control), or a planted fault.
"""
from __future__ import annotations

import numpy as np


class EngineError(RuntimeError):
    """The planner answered with another engine than the one asked for."""


class Program:
    def __init__(self, config: dict):
        from repro.core import CodeParams
        self.params = CodeParams.msr(n=config["n"], k=config["k"],
                                     d=config["d"], M=float(config["M"]))
        if int(round(self.params.alpha)) != config["alpha"]:
            raise ValueError(f"alpha = M/k = {self.params.alpha}, the "
                             f"configuration says {config['alpha']}")

    def plan(self, caps: np.ndarray, scheme: str):
        """``plan_many`` on the jit planner; any other engine is an error."""
        from repro.core import plan_many
        res = plan_many(caps, self.params, scheme, engine="jax")
        if res.engine != "jax":
            raise EngineError(f"plan_many planned {scheme} with engine "
                              f"{res.engine!r}, not jax")
        return res

    def plans(self, res):
        from repro.core import plans_from_batch
        return plans_from_batch(res, self.params)

    def kernel_matmul(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        from repro.kernels.ops import gf_matmul_numpy
        return gf_matmul_numpy(a, b)

    def store(self, file: np.ndarray, seed: int, matmul):
        """A coded store holding ``file``, encoded through ``matmul``.

        The simulator is made one byte wide and then given the benchmark's
        block group, so the data comes from the benchmark's seed and the
        encode of the full block group runs through ``matmul``."""
        from repro.coding import GF8, RLNC
        from repro.storage.simulator import RlncSimulator
        p = self.params
        sim = RlncSimulator(p, block_bytes=1, seed=seed)
        sim.rl = RLNC(GF8, matmul=matmul)
        sim.file_blocks = file
        sim.nodes = dict(enumerate(sim.rl.distribute(
            file, p.n, int(round(p.alpha)), sim.np_rng)))
        return sim
