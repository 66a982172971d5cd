"""Random linear network coding data plane (paper Section II-A).

A file of M blocks a_1..a_M is encoded into n*alpha coded blocks b_i =
sum_j c_ij a_j and spread over n nodes (alpha blocks each).  Every coded
block carries its length-M coding vector.  Regeneration, relaying and
reconstruction are all GF matrix multiplications on (coding-vector, payload)
pairs — the compute hot-spot accelerated by ``repro.kernels.gf_matmul``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np

from repro.obs.spans import span

from .gf import GF, GF8


@dataclasses.dataclass
class CodedBlocks:
    """A batch of coded blocks: coding vectors (num, M) + payload (num, B)."""

    vectors: np.ndarray   # (num, M) over GF
    payload: np.ndarray   # (num, block_bytes) over GF

    def __post_init__(self):
        assert self.vectors.shape[0] == self.payload.shape[0]

    @property
    def num(self) -> int:
        return self.vectors.shape[0]

    @staticmethod
    def join(parts: Sequence["CodedBlocks"]) -> "CodedBlocks":
        """The parts' rows in order, in new arrays; one part is returned
        as it is.  Span ``repro.store.concat`` with the ``bytes`` copied
        and the number of ``parts``."""
        if len(parts) == 1:
            return parts[0]
        nbytes = sum(p.vectors.nbytes + p.payload.nbytes for p in parts)
        with span("store.concat", bytes=nbytes, parts=len(parts)):
            return CodedBlocks(np.concatenate([p.vectors for p in parts]),
                               np.concatenate([p.payload for p in parts]))

    def concat(self, other: "CodedBlocks") -> "CodedBlocks":
        return CodedBlocks.join([self, other])


class RLNC:
    """Stateless coding operations over a chosen field."""

    def __init__(self, field: GF = GF8, matmul=None):
        self.field = field
        # pluggable GF matmul (e.g. the Pallas kernel wrapper); defaults to
        # the table-based numpy path.
        self._matmul = matmul if matmul is not None else field.matmul

    # -- file distribution ---------------------------------------------------

    def distribute(self, file_blocks: np.ndarray, n: int, alpha: int,
                   rng: np.random.Generator) -> List[CodedBlocks]:
        """Encode M file blocks into n nodes * alpha coded blocks (random
        linear code; MDS with probability -> 1 for large fields).

        The block group is encoded in one matmul; each node's rows are then
        copied out of the product into arrays of their own, so a node that
        is dropped frees its rows."""
        M = file_blocks.shape[0]
        C = self.field.random((n * alpha, M), rng)
        payload = self._matmul(C, file_blocks)
        return [CodedBlocks(C[i * alpha:(i + 1) * alpha].copy(),
                            payload[i * alpha:(i + 1) * alpha].copy())
                for i in range(n)]

    # -- regeneration --------------------------------------------------------

    def encode(self, blocks: CodedBlocks, num_out: int,
               rng: np.random.Generator) -> CodedBlocks:
        """num_out random combinations of ``blocks``: a provider's local
        blocks, or a relaying node's joined pool."""
        R = self.field.random((num_out, blocks.num), rng)
        return CodedBlocks(self._matmul(R, blocks.vectors),
                           self._matmul(R, blocks.payload))

    def relay(self, received: CodedBlocks, own: CodedBlocks, num_out: int,
              rng: np.random.Generator) -> CodedBlocks:
        """Interior tree node: re-encode (received ++ freshly generated own
        data) down to num_out blocks (Section V-A)."""
        return self.encode(CodedBlocks.join([received, own]), num_out, rng)

    def regenerate(self, received: CodedBlocks, alpha: int,
                   rng: np.random.Generator) -> CodedBlocks:
        """Newcomer: store alpha random combinations of everything received."""
        return self.encode(received, alpha, rng)

    # -- reconstruction --------------------------------------------------------

    def can_reconstruct(self, nodes: Sequence[CodedBlocks], M: int) -> bool:
        V = np.concatenate([nd.vectors for nd in nodes])
        return self.field.rank(V) >= M

    def reconstruct(self, nodes: Sequence[CodedBlocks], M: int) -> np.ndarray:
        """Recover the original M file blocks from >= M independent coded
        blocks (MDS reconstruction, Section II-A)."""
        V = np.concatenate([nd.vectors for nd in nodes])
        P = np.concatenate([nd.payload for nd in nodes])
        # pick M independent rows
        idx, r = [], 0
        work = np.array(V, dtype=np.int64, copy=True)
        picked = np.zeros((0, V.shape[1]), dtype=np.int64)
        for i in range(V.shape[0]):
            cand = np.concatenate([picked, work[i:i + 1]])
            if self.field.rank(cand) > r:
                picked, r = cand, r + 1
                idx.append(i)
                if r == M:
                    break
        if r < M:
            raise ValueError(f"rank {r} < M={M}: cannot reconstruct")
        A = V[idx]
        Y = P[idx]
        return self.field.solve(A, Y)
