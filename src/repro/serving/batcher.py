"""Continuous batching over the population axis.

The hot loop: every live request owns one LANE of a population dispatch,
and each serving step runs ``models.sru.forward_decode_step`` ONCE on the
whole mixed-allocation batch — lane *i*'s qp row (and hence its
scalar-prefetched menu index in the kernel lane) is request *i*'s
allocation. Admitting a request with a new allocation changes a gather
index, not the number of dispatches; there is no per-allocation fan-out
and zero requantization (the packed banks are shared, read-only).

Shape discipline: dispatch shapes are compile-bucketed. The lane axis is
padded to the next power-of-two bucket (pad lanes replicate a live lane's
qp row — every op is lane-independent, so pad lanes cost flops but cannot
perturb live lanes; their outputs are discarded). The time axis is NEVER
padded — the Bi-SRU backward recurrence reads future frames, so time
padding would contaminate real logits. Instead lanes are grouped per step
by their next-chunk length: full chunks (the steady state) form the one
main dispatch; ragged tail chunks (at most once per request lifetime) go
in a same-step extra dispatch per distinct length, keeping served logits
bitwise equal to the scalar ``forward(qp=)`` path on the same frames.

``SerialGroupBatcher`` is the measured counterfactual: the same engine
and step cadence, but each step fans out one dispatch PER ALLOCATION
GROUP — exactly what a naive "one compiled model per operating point"
server does: same logits, one dispatch per live allocation per step
(``tests/test_serving.py``).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import sru
from repro.serving.artifact import DeploymentArtifact
from repro.serving.metrics import RequestRecord, ServingLog, StepRecord
from repro.serving.router import RouteDecision, Router


@dataclass
class Request:
    """One inference request: ``feats`` (n_frames, input_dim) float32."""
    rid: int
    slo: str
    feats: np.ndarray


@dataclass
class _Flight:
    """A request in a lane: cursor into its frames + collected logits."""
    req: Request
    alloc: int
    rec: RequestRecord
    cursor: int = 0
    chunks: List[np.ndarray] = field(default_factory=list)

    def remaining(self) -> int:
        return self.req.feats.shape[0] - self.cursor

    def next_len(self, chunk: int) -> int:
        return min(chunk, self.remaining())


class ServingEngine:
    """Owns the loaded artifact's device state and the jitted step.

    Banks and the (bias-only) serving params are moved to device once;
    ``step`` runs one ``forward_decode_step`` dispatch. jax retraces per
    distinct (lanes, chunk_len) shape — the batcher's bucketing keeps
    that set small and steady-state traffic on one compiled executable.
    """

    def __init__(self, artifact: DeploymentArtifact, *,
                 use_kernel: bool = False):
        self.artifact = artifact
        self.cfg = artifact.cfg
        self.use_kernel = bool(use_kernel)
        self.banks = jax.tree_util.tree_map(jnp.asarray, artifact.banks)
        self.params = jax.tree_util.tree_map(jnp.asarray,
                                             artifact.serving_params())
        self._step = jax.jit(self._step_impl,
                             static_argnames=("use_kernel",))

    def _step_impl(self, feats, qp, use_kernel):
        return sru.forward_decode_step(self.params, self.cfg, feats, qp,
                                       banks=self.banks,
                                       use_kernel=use_kernel)

    def step(self, feats: np.ndarray, qp: np.ndarray) -> np.ndarray:
        """feats (P, T, m) + qp (P, L, 6) -> logits (P, T, n_outputs);
        blocks until the device result is ready (the batcher times this
        span as the step's compute latency)."""
        out = self._step(jnp.asarray(feats, jnp.float32),
                         jnp.asarray(qp, jnp.float32),
                         use_kernel=self.use_kernel)
        return np.asarray(jax.block_until_ready(out))

    def step_jaxpr(self, lanes: int, chunk: int):
        """Closed jaxpr of one serving step at a (lanes, chunk) bucket —
        the exact computation ``step`` dispatches, traced without running.
        This is what the C5 lane-independence prover consumes to certify
        that pad lanes and neighbors cannot perturb a live lane's logits
        (tests/test_serving.py proves it on a real loaded engine; the
        contract layer proves the same property on the registry harness)."""
        feats = jnp.zeros((lanes, chunk, self.cfg.input_dim), jnp.float32)
        qp = jnp.asarray(np.stack([self.artifact.qp[0]] * lanes))
        return jax.make_jaxpr(
            lambda f, q: self._step_impl(f, q, self.use_kernel))(feats, qp)


def bucket_for(n: int, buckets: List[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class ContinuousBatcher:
    """FIFO admission + per-step retire/admit over ``max_lanes`` lanes."""

    def __init__(self, engine: ServingEngine, router: Router, *,
                 max_lanes: int = 8, chunk: int = 16,
                 log: Optional[ServingLog] = None, collect: bool = False,
                 clock: Callable[[], float] = time.perf_counter):
        if max_lanes < 1:
            raise ValueError("need at least one lane")
        self.engine = engine
        self.router = router
        self.max_lanes = int(max_lanes)
        self.chunk = int(chunk)
        self.log = log if log is not None else ServingLog()
        self.collect = bool(collect)
        self.clock = clock
        self.queue: deque = deque()      # routed _Flight, awaiting a lane
        self.lanes: List[_Flight] = []   # in flight
        self.results: Dict[int, np.ndarray] = {}
        self._step_no = 0
        # power-of-two lane buckets: steady-state full batches compile once
        self.buckets = [1]
        while self.buckets[-1] < self.max_lanes:
            self.buckets.append(min(self.buckets[-1] * 2, self.max_lanes))

    # -- admission -------------------------------------------------------
    def submit(self, req: Request) -> RouteDecision:
        """Route + enqueue one request (or shed it at the door)."""
        decision = self.router.route(req.slo, queue_depth=len(self.queue))
        rec = self.log.add_request(RequestRecord(
            rid=req.rid, slo=req.slo, alloc=decision.alloc,
            t_enqueue=self.clock(), shed=decision.shed,
            degraded=decision.degraded, fallback=decision.fallback))
        if not decision.shed:
            self.queue.append(_Flight(req=req, alloc=decision.alloc,
                                      rec=rec))
        return decision

    def _admit(self):
        while self.queue and len(self.lanes) < self.max_lanes:
            self.lanes.append(self.queue.popleft())

    # -- the step loop ---------------------------------------------------
    def _dispatch_groups(self) -> List[List[_Flight]]:
        """Partition live lanes into same-shape dispatches: the full-chunk
        group (steady state: all of them -> ONE dispatch) plus one group
        per distinct ragged tail length."""
        by_len: Dict[int, List[_Flight]] = {}
        for fl in self.lanes:
            by_len.setdefault(fl.next_len(self.chunk), []).append(fl)
        return [by_len[t] for t in sorted(by_len, reverse=True)]

    def _dispatch(self, group: List[_Flight], t: int) -> Tuple[float, int]:
        """Run one padded dispatch for ``group`` (all next-chunk length
        ``t``); returns (compute span in seconds, lane bucket used)."""
        m = self.engine.cfg.input_dim
        bucket = bucket_for(len(group), self.buckets)
        feats = np.zeros((bucket, t, m), np.float32)
        # pad lanes replicate lane 0's qp row: a REAL allocation row, so
        # the bank gather index stays in range; their logits are dropped
        lanes_alloc = [fl.alloc for fl in group]
        lanes_alloc += [lanes_alloc[0]] * (bucket - len(group))
        qp = self.engine.artifact.qp_rows(lanes_alloc)
        for i, fl in enumerate(group):
            feats[i] = fl.req.feats[fl.cursor:fl.cursor + t]
        t0 = self.clock()
        for fl in group:
            if fl.rec.t_start is None:
                fl.rec.t_start = t0
        logits = self.engine.step(feats, qp)
        span = self.clock() - t0
        for i, fl in enumerate(group):
            if self.collect:
                fl.chunks.append(logits[i])
            fl.cursor += t
            fl.rec.tokens += t
        return span, bucket

    def step(self) -> int:
        """One serving step: admit -> dispatch live lanes -> retire.
        Every live lane advances one chunk; the step logs ONE StepRecord
        whose ``n_dispatches`` counts the dispatches it took (1 in steady
        state for continuous batching; the ragged-tail or serial-baseline
        extras otherwise). Returns the number of live lanes computed."""
        self._admit()
        if not self.lanes:
            return 0
        self._step_no += 1
        tokens, span, max_bucket, n_disp = 0, 0.0, 0, 0
        for group in self._dispatch_groups():
            t = group[0].next_len(self.chunk)
            s, bucket = self._dispatch(group, t)
            span += s
            tokens += t * len(group)
            max_bucket = max(max_bucket, bucket)
            n_disp += 1
        self.log.add_step(StepRecord(
            step=self._step_no, n_lanes=len(self.lanes), bucket=max_bucket,
            tokens=tokens, compute_s=span, n_dispatches=n_disp))
        done = self.clock()
        still = []
        for fl in self.lanes:
            if fl.remaining() == 0:
                fl.rec.t_done = done
                if self.collect:
                    self.results[fl.req.rid] = np.concatenate(fl.chunks)
            else:
                still.append(fl)
        n = len(self.lanes)
        self.lanes = still
        return n

    def run_until_idle(self, max_steps: int = 100000) -> ServingLog:
        """Drain the queue and all lanes; returns the log."""
        steps = 0
        while self.queue or self.lanes:
            if self.step() == 0 and not self.queue:
                break
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"batcher did not drain in {max_steps} "
                                   f"steps")
        return self.log


class SerialGroupBatcher(ContinuousBatcher):
    """Naive per-allocation-group serving baseline (same engine).

    Identical admission, lanes, chunking and retire semantics — but each
    step issues one dispatch PER ALLOCATION present in the batch, the way
    a server with one compiled model per operating point must. On a mixed
    front this multiplies the per-step fixed costs (dispatch, scan
    overhead, partially-filled buckets) by the number of live
    allocations.
    """

    def _dispatch_groups(self) -> List[List[_Flight]]:
        by_key: Dict[tuple, List[_Flight]] = {}
        for fl in self.lanes:
            key = (fl.next_len(self.chunk), fl.alloc)
            by_key.setdefault(key, []).append(fl)
        return [by_key[k] for k in sorted(by_key, reverse=True)]
