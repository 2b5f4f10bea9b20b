"""Data-parallel replica router: whole-engine replicas over device slices.

Tensor parallelism (``parallel/tp.py``) scales ONE engine across the
``model`` axis of its mesh; this module scales *throughput* the orthogonal
way — R independent ``PagedServingEngine`` replicas, each owning a
disjoint slice of ``jax.devices()`` (``make_replicas``), each running its
own continuous-batching ``Scheduler`` loop. The two compose: a replica
may itself be an M-way TP engine, so R x M devices serve as R replicas
of M shards (the paper's bank-parallel shared memory tiled twice over).

Routing policies (``policy=``):

* ``"hash"`` — ``rid % R``: stateless, sticky (a resubmitted/preempted
  request lands on the replica that still caches its prefix), the
  default.
* ``"least_loaded"`` — the replica with the fewest in-flight tokens
  (queued prompt+budget plus live slots' outstanding work) at submit
  time: better tail latency under skewed traffic, at the cost of losing
  prefix-cache affinity.

``step()`` ticks every replica once (round-robin fairness is the trivial
kind: each tick advances every live replica exactly one scheduling
round); ``drain`` bounds the *per-replica* step budget like
``Scheduler.drain``. ``stats()`` rolls up per-replica allocator /
telemetry counters with ``replicas x`` totals plus the per-replica
breakdown, so pool pressure on one replica is visible rather than
averaged away.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax

from repro.runtime.scheduler import Scheduler, SchedulerExhausted
from repro.runtime.serving import Request

_POLICIES = ("hash", "least_loaded")


def make_replicas(cfg, params, *, replicas: int = 1, model: int = 1,
                  devices: Optional[Sequence] = None, **engine_kwargs
                  ) -> "ReplicaRouter":
    """Build R paged engines on disjoint ``model``-wide device slices and
    wrap them in a router. ``replicas * model`` must not exceed the
    visible device count; ``model == 1`` builds plain single-shard
    engines (no mesh), each with its weights, page pool and bookkeeping
    placed on its own device, so the replicas never share a chip."""
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.serving import PagedServingEngine

    devs = list(devices) if devices is not None else list(jax.devices())
    policy = engine_kwargs.pop("policy", "hash")
    need = replicas * model
    if replicas < 1 or need > len(devs):
        raise ValueError(
            f"make_replicas: {replicas} replica(s) x {model} shard(s) "
            f"need {need} device(s), have {len(devs)}")
    engines = []
    for i in range(replicas):
        slice_ = devs[i * model:(i + 1) * model]
        if model > 1:
            mesh = make_host_mesh(model=model, devices=slice_)
            engines.append(PagedServingEngine(cfg, params, mesh=mesh,
                                              **engine_kwargs))
            continue
        # one-device replica: commit the weights to the device and build
        # the pool and bookkeeping under it; every program then follows
        # its committed operands there
        with jax.default_device(slice_[0]):
            engines.append(PagedServingEngine(
                cfg, jax.device_put(params, slice_[0]), **engine_kwargs))
    return ReplicaRouter(engines, policy=policy)


class ReplicaRouter:
    """Dispatch requests across replica engines; one Scheduler each."""

    def __init__(self, engines: List, *, policy: str = "hash"):
        if not engines:
            raise ValueError("ReplicaRouter needs at least one engine")
        if policy not in _POLICIES:
            raise ValueError(
                f"policy must be one of {_POLICIES}: {policy!r}")
        self.engines = list(engines)
        self.scheds = [Scheduler(e) for e in self.engines]
        self.policy = policy
        self.routed: List[int] = [0] * len(self.engines)

    # -- routing ----------------------------------------------------------
    def _load(self, i: int) -> int:
        """In-flight token estimate for replica i: queued work plus what
        its live slots still owe."""
        sched, eng = self.scheds[i], self.engines[i]
        queued = sum(len(r.prompt) + r.max_new - len(r.generated)
                     for r in sched.pending)
        live = sum(len(r.prompt) + r.max_new
                   for r in getattr(eng, "live", []) if r is not None)
        return queued + live

    def _pick(self, req: Request) -> int:
        if self.policy == "hash":
            return req.rid % len(self.engines)
        return min(range(len(self.engines)), key=self._load)

    def submit(self, req: Request) -> None:
        """Route and enqueue (admission happens on the replica's next
        tick, so a momentarily-full replica queues rather than drops)."""
        i = self._pick(req)
        self.routed[i] += 1
        tr = getattr(self.engines[i], "trace", None)
        if tr:
            tr.instant("dispatch", tid="router",
                       args={"rid": req.rid, "replica": i,
                             "policy": self.policy})
        self.scheds[i].add(req)

    add = submit                      # Scheduler-compatible spelling

    # -- driving ----------------------------------------------------------
    def has_work(self) -> bool:
        return any(s.pending or s.engine.has_live() for s in self.scheds)

    def step(self) -> None:
        """One round: tick every replica that has work. Replicas are
        independent single-engine loops — the router adds no cross-replica
        sync; a tick is host-sequential here, concurrent across hosts in
        a real deployment."""
        for s in self.scheds:
            if s.pending or s.engine.has_live():
                s.tick()

    def drain(self, max_steps: int = 10_000) -> None:
        """Run until every replica is empty; ``max_steps`` bounds each
        replica's OWN budget (Scheduler.drain semantics), so one wedged
        replica fails loudly instead of starving the loop."""
        rounds = 0
        while self.has_work():
            if rounds >= max_steps:
                busy = [i for i, s in enumerate(self.scheds)
                        if s.pending or s.engine.has_live()]
                raise SchedulerExhausted(
                    f"router drain exhausted {max_steps} rounds with "
                    f"replica(s) {busy} still busy")
            self.step()
            rounds += 1

    def run_to_completion(self, requests: List[Request],
                          max_steps: int = 10_000) -> List[Request]:
        for r in requests:
            self.submit(r)
        self.drain(max_steps)
        return [r for r in requests if r.done]

    # -- telemetry --------------------------------------------------------
    def stats(self) -> Dict[str, object]:
        """Rolled-up telemetry: totals across replicas + per-replica
        breakdowns (peak pages per replica per shard is the capacity-
        planning number; a total would hide the hot replica)."""
        pool = [e.pool_stats() for e in self.engines]
        shard = [e.shard_stats() for e in self.engines]
        return {
            "replicas": len(self.engines),
            "policy": self.policy,
            "routed": list(self.routed),
            "decode_steps": sum(e.decode_steps for e in self.engines),
            "decoded_tokens": sum(e.decoded_tokens for e in self.engines),
            "preempted": sum(s.preempted for s in self.scheds),
            "peak_pages_per_replica": [p.peak_pages for p in pool],
            "allocated_pages_per_replica": [p.allocated_pages
                                            for p in pool],
            "model_shards": [s["model_shards"] for s in shard],
            "peak_pages_per_shard": [s["peak_pages_per_shard"]
                                     for s in shard],
        }
