"""Pareto-front-as-a-service: serve live traffic across a stored MOHAQ front.

The search half of this repo produces a *front* of operating points and
``tools/convert_checkpoint.py`` freezes them into one packed deployment
artifact (int weight containers + per-allocation quantization-grid rows).
This package is the runtime that dispatches from it:

- ``artifact``  loads the deployment once and exposes the shared packed
                banks plus per-allocation menu-index/qp rows and objective
                metadata;
- ``router``    maps each request's SLO class to an allocation (accuracy /
                latency tiers over the front), with admission control and
                load-shed fallback to cheaper allocations;
- ``batcher``   the continuous-batching step loop whose hot path is ONE
                ``forward_decode_step`` dispatch per step — the population
                axis of the search substrate is repurposed as the REQUEST
                axis, so lane *i*'s menu index is request *i*'s allocation
                (zero requantization, no per-allocation dispatch fan-out);
- ``metrics``   per-request queue/compute/total latency and tokens/sec in a
                structured log (``ServingLog.summary``).

The population-axis-as-request-axis contract: every per-lane quantity the
search stacks for P *candidates* (qp grid rows, menu indices, bank gathers)
is reused unchanged for P *requests* — the only new degree of freedom is
per-lane input features (``feats`` of shape (P, T, m) instead of a
broadcast (B, T, m)), which ``models.sru.forward_decode_step`` threads
through the same banked/kernel lanes. Parity carries over: lane
*i*'s served logits are bitwise equal to the scalar ``forward(qp=...)``
path on the same chunk.
"""
from repro.serving.artifact import (DeploymentArtifact, alloc_cost_bits,
                                    load_deployment, qp_stack,
                                    serving_params)
from repro.serving.batcher import (ContinuousBatcher, Request,
                                   SerialGroupBatcher, ServingEngine)
from repro.serving.metrics import RequestRecord, ServingLog, StepRecord
from repro.serving.router import (RouteDecision, Router, SLOClass,
                                  default_classes)

__all__ = [
    "ContinuousBatcher", "DeploymentArtifact", "Request", "RequestRecord",
    "RouteDecision", "Router", "SLOClass", "SerialGroupBatcher",
    "ServingEngine", "ServingLog", "StepRecord", "alloc_cost_bits",
    "default_classes", "load_deployment", "qp_stack", "serving_params",
]
