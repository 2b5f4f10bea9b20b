"""Serving engines: paged KV-cache continuous batching (default) and the
legacy dense-slot engine (baseline / fallback for recurrent stacks).

``PagedServingEngine`` is the software analogue of Voltra's shared-memory
architecture (PAPER.md):

* **Dynamic allocation** — full-attention KV lives in a shared page pool
  (``models/api.paged_cache_init``) addressed through per-request block
  tables (``runtime/kv_cache.PageAllocator``). Pages are allocated on
  demand as decode crosses page boundaries and reclaimed on finish, so
  allocated capacity tracks *live tokens*, not ``slots * max_len``.
* **Mixed-grained prefetch** — prompts are right-padded to power-of-two
  length buckets, so ``jax.jit`` traces the prefill once per bucket
  instead of once per distinct prompt length (the dense engine's
  pathology on mixed-length traffic).
* **Shared-memory access efficiency** — ``step()`` keeps position / EOS /
  budget bookkeeping on device and does ONE host sync per step (a single
  ``device_get`` of (tokens, done)), where the dense engine pays one sync
  per live slot per step.
* **Prefix sharing** (``prefix_cache=True``) — concurrent requests with a
  common prompt prefix (system prompts, few-shot templates, multi-turn
  history) alias the SAME physical pages: admission takes the longest
  cached prefix from a radix tree (``runtime/prefix_cache.py``), prefill
  runs on the suffix only, and pages are refcounted with copy-on-write on
  mid-page divergence and LRU eviction of idle cached pages under pool
  pressure. Both decode attention impls work unchanged — block tables
  already indirect through physical pages.
* **Tensor parallelism** (``mesh=``, ISSUE 6) — pass a ``("data",
  "model")`` mesh (``launch/mesh.make_host_mesh``) and the engine shards
  its KV pools and attn/mlp weights over KV heads on the ``model`` axis
  (``parallel/tp.py``): each shard owns its GQA groups' slice of every
  page, block tables / lengths / bookkeeping stay replicated, and each
  traced program wraps exactly its model call + pool scatter in ONE
  ``shard_map`` boundary — sampling and bookkeeping stay outside the
  manual region, so the one-host-sync-per-step contract and every
  feature above (prefix cache, speculative decode, hybrid stacks)
  compose with sharding unchanged. Data parallelism layers on top as
  whole-engine replicas (``runtime/router.py``).

* **Host-tier KV pages with prefetch streamers** (``host_tier=True``,
  ISSUE 7) — a second memory level under the device pool
  (``runtime/host_tier.py``): cold pages DEMOTE to a NumPy-backed host
  store instead of being destroyed, and a copy stream prefetches them
  back one scheduler tick ahead. Three demotion sources replace today's
  destructive paths: idle prefix-cache pages demote before LRU-evicting
  (a radix hit on a host-resident node promotes instead of
  re-prefilling), preempted requests swap out their whole table AND
  their recurrent state slots (resume = promote + scatter + state
  import — NO re-prefill), and slid-out window pages are archived. The
  streamer is mixed-grained like the paper's: page-granular readahead
  for radix promotions, request-granular bulk restore for swap-ins.
  Net: a working set ≫ the device pool serves with zero output change
  (``serve_bench --scenario oversubscribe``). Single-shard only for
  now (``mesh=`` and ``host_tier=`` are mutually exclusive).

* **Hybrid / windowed / recurrent stacks** are first-class since ISSUE 5:
  sliding-window layers (``local_attn``) get *paged ring buffers with
  page recycling* — a second block table whose pages are freed the moment
  they slide entirely out of the attention window
  (``PageAllocator.release_prefix``), bounding live KV at O(window) pages
  per request instead of O(max_len); the flash-decode kernel masks and
  skips below-window pages (``kernels/paged_attention.py`` ``window``).
  Recurrent layers (``ssm`` / ``rglru``) get *fixed-size state slots*
  beside the page pool — written by (bucket-padded, state-masked)
  prefill at admission, rebuilt by re-prefill on preemption-resume, and
  rolled back on speculative rejection by gathering the verify step's
  per-row state checkpoints (``_select_fn``). Continuous batching,
  bucketed prefill, preemption and ``spec_k`` therefore all work on
  griffin-style hybrids.

``DenseServingEngine`` is the seed engine, kept verbatim as the measured
baseline (benchmarks/serve_bench.py): dense max_len lanes, window-sized
ring buffers, per-length prefill retraces. ``ServingEngine(cfg, ...)``
picks the paged engine for every servable block pattern and falls back to
dense — loudly — only for encoder-decoder stacks.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.paged_attention import pages_per_block
from repro.models import api
from repro.models import transformer as tfm
from repro.models.layers import is_dropless
from repro.parallel.sharding import NO_RULES, Rules
from repro.parallel.tp import tp_plan
from repro.runtime.drafter import ngram_propose
from repro.runtime.host_tier import HostTier, SwapRecord, _tree_nbytes
from repro.runtime.kv_cache import SCRATCH_PAGE, PageAllocator, PoolStats
from repro.runtime.prefix_cache import PrefixCache, PrefixMatch
from repro.runtime.sampling import (ACCEPT_DRAW, NEG_FILTER, SAMPLE_DRAW,
                                    SamplingParams, draw_keys, fold_keys,
                                    policy_operands, request_params,
                                    sample_rows, scale_mask)
from repro.runtime.trace import NOOP_SPAN, Tracer, default_tracer, percentile


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    preemptions: int = 0
    # per-request decode policy (runtime/sampling.py); None = the
    # engine's default. Carried from submit() into the traced step as
    # batched operands — greedy and sampled requests share one trace.
    params: Optional[SamplingParams] = None


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _pageable(cfg) -> bool:
    """Whether the paged engine can host this stack: full attention,
    sliding-window attention (paged ring buffers with page recycling) and
    recurrent state (fixed-size slots) are all servable; only
    encoder-decoder stacks fall back to the dense engine."""
    return set(tfm.pattern_for(cfg)) <= set(api.PAGED_SERVABLE_KINDS)


def expert_series(rows) -> Dict[str, float]:
    """The ``experts`` counter's series for one decode step from its
    routed rows per held expert, (routed layers, held): rows per held
    expert (mean and max over layers and experts) and the held experts
    hit per layer (mean). Every slot's row counts, a dead slot's too: the
    step routes and computes it."""
    r = np.asarray(rows)
    return {"rows_mean": float(r.mean()), "rows_max": float(r.max()),
            "hit": float((r > 0).sum(1).mean())}


def _win_rid(rid: int):
    """Allocator key of a request's sliding-window block table (kept
    separate from its full-attention table: the two recycle and roll
    back independently)."""
    return ("win", rid)


def _spec_uses(spec: P, axis: str) -> bool:
    """Whether a PartitionSpec shards any dim over mesh axis ``axis``."""
    return any(e == axis or (isinstance(e, tuple) and axis in e)
               for e in spec)


def _run_to_completion(engine, requests: List[Request],
                       max_steps: int) -> List[Request]:
    """Shared drive loop for both engines, routed through the Scheduler so
    an exhausted step budget fails loudly (SchedulerExhausted) instead of
    silently returning truncated outputs."""
    from repro.runtime.scheduler import Scheduler
    sched = Scheduler(engine)
    for r in requests:
        sched.add(r)
    sched.drain(max_steps=max_steps)
    return [r for r in requests if r.done]


class ServingMetricsMixin:
    """Shared observability layer for both engines (ISSUE 8): request
    lifecycle bookkeeping (arrival / first token / last token), the timed
    ``submit``/``step`` wrappers that feed the tracer and the wall-clock
    accumulators, and the unified ``metrics()`` snapshot.

    The engine class provides ``_submit`` / ``_step`` (the untimed
    implementations) plus the five ``*_stats()`` methods; the mixin owns
    everything that used to be duplicated between ``DenseServingEngine``
    and ``PagedServingEngine`` — ``decode_steps`` / ``decoded_tokens`` /
    ``step_wall_s`` / ``first_token_at`` — and adds:

    * ``tick_wall_s`` — wall time of whole decode ticks (only ticks with
      live slots count, so an idle scheduler doesn't dilute the ratio);
    * **temporal utilization** = ``step_wall_s / tick_wall_s``: the
      fraction of each decode tick spent in the device program (dispatch
      + the one host sync) rather than host-side bookkeeping, draft,
      rollback or tier traffic — the serving-level analogue of the
      paper's temporal-utilization metric (compute cycles over total
      cycles; Fig. 6's 2.12-2.94x win is this ratio moved by prefetch).

    TTFT is arrival -> first emitted token, where *arrival* is the
    earliest of ``Scheduler.add`` (queue wait included) and the first
    ``submit`` (direct-submit callers). TPOT is (last - first) /
    (tokens - 1) per request with >= 2 tokens. Percentiles are computed
    on demand in ``metrics()``; per-request stamps live in plain dicts.
    """

    def _init_metrics(self, tracer: Optional[Tracer]) -> None:
        """Engine-constructor hook: install the tracer (falling back to
        the process default — ``trace.set_default_tracer`` — so bench
        harnesses can turn on tracing for every engine they build) and
        zero every counter the mixin owns."""
        self.trace = tracer if tracer is not None else default_tracer()
        self.decode_steps = 0
        self.decoded_tokens = 0
        self.step_wall_s = 0.0        # device dispatch + sync, decode only
        self.tick_wall_s = 0.0        # whole decode ticks (live slots only)
        self.first_token_at: Dict[int, float] = {}
        self._arrival_at: Dict[int, float] = {}
        self._last_token_at: Dict[int, float] = {}
        self._tokens_emitted: Dict[int, int] = {}

    # -- request lifecycle -------------------------------------------------

    def note_arrival(self, rid: int) -> None:
        """Stamp a request's arrival (idempotent — the earliest stamp
        wins). ``Scheduler.add`` calls this on enqueue so TTFT includes
        queue wait; ``submit`` calls it too as the fallback for callers
        that drive the engine directly."""
        if rid not in self._arrival_at:
            self._arrival_at[rid] = time.perf_counter()
            self.trace.begin_async("request", rid)

    def _note_emitted(self, rid: int, n: int = 1) -> None:
        now = time.perf_counter()
        if rid not in self.first_token_at:
            self._arrival_at.setdefault(rid, now)
            self.first_token_at[rid] = now
            if self.trace:
                self.trace.instant("first_token", args={"rid": rid})
        self._last_token_at[rid] = now
        self._tokens_emitted[rid] = self._tokens_emitted.get(rid, 0) + n

    def _note_finished(self, rid: int) -> None:
        self.trace.end_async("request", rid)

    # -- timed wrappers ----------------------------------------------------

    def submit(self, req: Request) -> bool:
        """Admit ``req`` (see the engine's ``_submit`` for semantics),
        timed and traced."""
        self.note_arrival(req.rid)
        tr = self.trace
        with tr.span("admit", args={"rid": req.rid} if tr else None):
            return self._submit(req)

    def step(self) -> List[Request]:
        """Advance every live slot (see the engine's ``_step``), timed and
        traced. Idle ticks (no live slots — e.g. everything still queued)
        run untimed so ``tick_wall_s`` divides only real decode work."""
        if not self.has_live():
            return self._step()
        t0 = time.perf_counter()
        with self.trace.span("decode_tick"):
            out = self._step()
        self.tick_wall_s += time.perf_counter() - t0
        return out

    # -- the unified snapshot ----------------------------------------------

    def _latency_samples(self):
        ttfts = [t - self._arrival_at[rid]
                 for rid, t in self.first_token_at.items()
                 if rid in self._arrival_at]
        tpots = []
        for rid, n in self._tokens_emitted.items():
            if n > 1 and rid in self.first_token_at:
                tpots.append((self._last_token_at[rid]
                              - self.first_token_at[rid]) / (n - 1))
        return ttfts, tpots

    def metrics(self) -> Dict[str, object]:
        """One flat snapshot of everything, under stable namespaced keys:
        ``engine.*`` (throughput counters), ``latency.*`` (TTFT / TPOT
        percentiles, seconds), ``util.*`` (wall-clock split + temporal
        utilization), and every subsystem's stats under ``pool.*`` /
        ``spec.*`` / ``prefix.*`` / ``tier.*`` / ``shard.*``. The key set
        is IDENTICAL across engines and configurations — subsystems that
        are off report zeros, never missing keys — so CSV columns and
        dashboards line up between runs (tests/test_metrics.py)."""
        ttfts, tpots = self._latency_samples()
        tick = self.tick_wall_s
        m: Dict[str, object] = {
            "engine.kind": type(self).__name__,
            "engine.decode_steps": float(self.decode_steps),
            "engine.decoded_tokens": float(self.decoded_tokens),
            "engine.prefill_traces": float(self.prefill_traces),
            "latency.requests": float(len(ttfts)),
            "latency.ttft_p50_s": percentile(ttfts, 0.50),
            "latency.ttft_p95_s": percentile(ttfts, 0.95),
            "latency.ttft_mean_s": (sum(ttfts) / len(ttfts)
                                    if ttfts else 0.0),
            "latency.tpot_p50_s": percentile(tpots, 0.50),
            "latency.tpot_p95_s": percentile(tpots, 0.95),
            "latency.tpot_mean_s": (sum(tpots) / len(tpots)
                                    if tpots else 0.0),
            "util.step_wall_s": self.step_wall_s,
            "util.tick_wall_s": tick,
            "util.temporal": self.step_wall_s / tick if tick > 0 else 0.0,
        }
        for ns, stats in (
                ("pool", dataclasses.asdict(self.pool_stats())),
                ("spec", self.spec_stats()),
                ("prefix", self.prefix_stats()),
                ("tier", self.tier_stats()),
                ("shard", self.shard_stats()),
                ("sampling", self.sampling_stats())):
            for k, v in stats.items():
                m[f"{ns}.{k}"] = float(v) if isinstance(v, int) else v
        return m

    def reset_metrics(self) -> None:
        """The single warm-up reset point (benchmarks call this between
        the cache-warming pass and the timed replay): zero every latency
        and wall-clock counter the mixin owns, then the engine's own
        subsystem counters (``_reset_subsystem_counters``). Trace events
        are NOT discarded — a ``reset_metrics`` instant marks the
        boundary instead, so a trace of warm-up + replay stays one
        coherent timeline. jit trace caches (``prefill_traces`` /
        seen-bucket sets) survive too: retrace identity is a lifetime
        fact, not a per-phase rate."""
        self.decode_steps = 0
        self.decoded_tokens = 0
        self.step_wall_s = 0.0
        self.tick_wall_s = 0.0
        self.first_token_at.clear()
        self._arrival_at.clear()
        self._last_token_at.clear()
        self._tokens_emitted.clear()
        self.trace.instant("reset_metrics")
        self._reset_subsystem_counters()

    def _count_tokens(self, pol: Optional[SamplingParams], n: int) -> None:
        """Attribute ``n`` emitted tokens to the greedy or sampled bucket
        of ``sampling_stats`` (``pol`` is the emitting slot's policy)."""
        if pol is None or pol.is_greedy:
            self.greedy_tokens += n
        else:
            self.sampled_tokens += n

    def _reset_subsystem_counters(self) -> None:
        pass                          # engines with extra counters override


def ServingEngine(cfg, params, **kwargs):
    """Engine factory: paged engine for every servable block pattern —
    full attention, sliding-window (local_attn) and recurrent (ssm/rglru)
    layers included — dense-slot engine only for encoder-decoder stacks.

    A dense fallback cannot honor the paged feature kwargs. Dropping them
    silently (the pre-ISSUE-5 behavior) meant a caller who asked for
    speculative decode or prefix sharing got neither and no signal; now
    every dropped kwarg whose value differs from the paged engine's
    default — i.e. the caller actually asked for something — is named in
    a warning, and a truthy ``spec_k``, which changes the output contract
    (verify-step semantics, ``spec_stats``), raises instead. Kwargs still
    at their defaults drop quietly: launchers pass the full knob set
    unconditionally, and warning on never-requested features would turn
    the loud-fallback signal into noise."""
    if _pageable(cfg):
        return PagedServingEngine(cfg, params, **kwargs)
    paged_defaults = {"page_size": 16, "num_pages": None,
                      "attn_impl": "kernel", "prefix_cache": False,
                      "spec_k": 0, "spec_ngram": 3, "drafter": None,
                      "mesh": None, "host_tier": False}
    dropped = []
    for k, default in paged_defaults.items():
        if k in kwargs:
            v = kwargs.pop(k)
            if k == "spec_k" and v:
                raise ValueError(
                    f"spec_k={v} requested, but {cfg.name!r} "
                    f"(pattern {tfm.pattern_for(cfg)}) is not servable by "
                    f"the paged engine and the dense fallback has no "
                    f"speculative decode — drop spec_k or serve a paged-"
                    f"servable stack")
            if k == "drafter" and v is not None:
                raise ValueError(
                    f"a drafter was passed, but {cfg.name!r} (pattern "
                    f"{tfm.pattern_for(cfg)}) is not servable by the paged "
                    f"engine and the dense fallback has no speculative "
                    f"verify step to feed it — drop the drafter or serve "
                    f"a paged-servable stack")
            if v != default:
                dropped.append(f"{k}={v!r}")
    if dropped:
        warnings.warn(
            f"{cfg.name!r} (pattern {tfm.pattern_for(cfg)}) falls back to "
            f"DenseServingEngine, which ignores the paged-engine "
            f"kwarg(s) {dropped} — the features they configure will NOT "
            f"be active", stacklevel=2)
    return DenseServingEngine(cfg, params, **kwargs)


# ===========================================================================
# Paged engine
# ===========================================================================


class PagedServingEngine(ServingMetricsMixin):
    """Continuous batching over a paged KV cache with bucketed prefill."""

    def __init__(self, cfg, params, *, slots: int = 4, max_len: int = 256,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 rules: Rules = NO_RULES, eos_id: int = -1,
                 temperature: float = 0.0, seed: int = 0,
                 sampling: Optional[SamplingParams] = None,
                 attn_impl: str = "kernel", prefix_cache: bool = False,
                 spec_k: int = 0, spec_ngram: int = 3, drafter=None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 host_tier: bool = False,
                 tracer: Optional[Tracer] = None):
        if not _pageable(cfg):
            raise ValueError(
                f"paged serving cannot host pattern "
                f"{tfm.pattern_for(cfg)}; use DenseServingEngine")
        assert page_size >= 1 and page_size & (page_size - 1) == 0, \
            "page_size must be a power of two"
        if attn_impl not in ("kernel", "gather"):
            raise ValueError(f"attn_impl must be kernel|gather: {attn_impl}")
        if drafter is not None and not spec_k:
            raise ValueError(
                "a drafter only runs inside the speculative verify step — "
                "pass spec_k > 0 with it (or drop the drafter)")
        if host_tier and mesh is not None:
            raise ValueError(
                "host_tier=True is single-shard only: swap blobs would "
                "have to gather/scatter each shard's KV-head slice through "
                "the manual boundary — TP + tiering is an open item")
        # block-kind split: full-attention layers share one block table,
        # sliding-window layers a second (recycled) one, recurrent layers
        # hold fixed-size per-slot state beside the pool
        self._kinds = tuple(tfm.pattern_for(cfg))
        _, self._tail = tfm.layer_plan(cfg)
        present = set(self._kinds) | set(self._tail)
        self.has_full = bool(present & set(api.PAGEABLE_KINDS))
        self.has_win = bool(present & set(api.WINDOW_KINDS))
        self.has_state = bool(present & set(api.STATE_KINDS))
        # dropless routed layers: the plain step also returns the routed
        # rows of each held expert (the tracer's ``experts`` counter)
        self._routes = (is_dropless(cfg) and mesh is None
                        and any(k.endswith("_moe") for k in present))
        self.window = cfg.hybrid.window if self.has_win else 0
        if prefix_cache and (self.has_win or self.has_state):
            raise ValueError(
                "prefix_cache needs an attention-only stack: recurrent "
                "state cannot be reconstructed from shared KV pages, and "
                "window pages are recycled per-request")
        if self.has_win and self.window < 1:
            raise ValueError("local_attn layers need cfg.hybrid.window >= 1")
        # decode attention impl rides on the (frozen) config so it reaches
        # layers.attention_decode through the jitted step without an extra
        # traced operand; "kernel" = in-kernel block-table gather (Pallas
        # flash-decode), "gather" = PR-1 dense pool gather (bench baseline)
        cfg = dataclasses.replace(cfg, paged_attn_impl=attn_impl)
        self.attn_impl = attn_impl
        self.cfg, self.params = cfg, params
        self.page_size = page_size
        self.max_len = -(-max_len // page_size) * page_size
        self.max_blocks = self.max_len // page_size
        self.slots = slots
        self.rules, self.eos_id = rules, eos_id
        # decode policy: `sampling` is the engine default for requests
        # without their own params; the legacy `temperature` kwarg builds
        # one when `sampling` isn't given. Per-slot policies ride into
        # every traced program as stacked operands (runtime/sampling.py),
        # so a mixed greedy/sampled batch shares one trace.
        self.default_params = (
            sampling if sampling is not None
            else SamplingParams(temperature=temperature)).validate()
        self.temperature = self.default_params.temperature
        self.seed = int(seed) & 0x7FFFFFFF
        self._policy: List[Optional[SamplingParams]] = [None] * slots
        self._rid_host = [0] * slots          # rid per slot (PRNG fold)
        self._samp_idx = [0] * slots          # next generated-token index
        self._init_metrics(tracer)    # tracer + shared latency counters

        # tensor parallelism: one TPPlan per (config, mesh) decides what
        # shards (parallel/tp.py) — KV-head pools and attn/mlp weights over
        # the mesh's "model" axis, everything else replicated. mesh=None is
        # the single-shard engine, byte-for-byte the pre-TP code paths.
        # Inside shard_map bodies the model uses the plan's ManualRules
        # (explicit psum at the two contraction points); the GSPMD `rules`
        # kwarg keeps steering the non-TP path.
        self.tp = tp_plan(cfg, mesh)
        self._model_rules = self.tp.rules if self.tp is not None else rules
        if self.tp is not None:
            self._param_specs = self.tp.param_specs(cfg)
            self.params = self.tp.put(self.params, self._param_specs)

        usable = num_pages if num_pages is not None \
            else slots * self.max_blocks
        self.alloc = PageAllocator(usable, page_size)
        # prefix sharing: radix tree over page-aligned token chunks mapping
        # to refcounted physical pages (runtime/prefix_cache.py). Off by
        # default: sharing keeps refcount-0 pages cached in the pool, which
        # callers that meter allocated_pages must opt into.
        self.prefix: Optional[PrefixCache] = \
            PrefixCache(self.alloc, tracer=self.trace) \
            if prefix_cache else None
        # two-tier memory hierarchy: host-RAM page store + copy stream
        # (runtime/host_tier.py). Off by default — demotion keeps blobs
        # alive in host RAM, which callers that meter memory opt into.
        self.tier: Optional[HostTier] = \
            HostTier(tracer=self.trace) if host_tier else None
        # pool row 0 is the scratch page -> usable + 1 physical rows
        self.cache = api.paged_cache_init(cfg, slots, usable + 1, page_size)
        if self.tp is not None:
            # shard the pools over KV heads at rest: block tables stay
            # replicated (logical pages are a host-side fact), each shard
            # owns its GQA groups' slice of EVERY page
            self._cache_specs = self.tp.cache_specs(cfg, self.cache)
            self.cache = self.tp.put(self.cache, self._cache_specs)
        # tokens per KV block the attention kernel streams on a full
        # layer, from the pool shape each shard holds (the tracer's
        # kv_blocks series); None where no full layer runs the kernel
        self._kv_block_tokens = None
        if self.has_full and attn_impl == "kernel":
            kv = cfg.kv_heads
            if self.tp is not None and "kv_heads" in self.tp.sharded_axes:
                kv //= self.tp.model_shards
            self._kv_block_tokens = page_size * pages_per_block(
                page_size, kv, cfg.resolved_head_dim,
                jnp.dtype(cfg.kv_cache_dtype).itemsize,
                min(self.max_blocks, usable + 1))
        self.block_table = jnp.zeros((slots, self.max_blocks), jnp.int32)
        # sliding-window block table: logical block j still means absolute
        # positions [j*page, (j+1)*page), but entries that slid below the
        # window are recycled back to SCRATCH (the kernel skips them)
        self.win_table = jnp.zeros((slots, self.max_blocks), jnp.int32)
        self.pos = jnp.zeros((slots,), jnp.int32)
        self.cur_tok = jnp.zeros((slots, 1), jnp.int32)
        self.live_mask = jnp.zeros((slots,), bool)
        self.gen_cnt = jnp.zeros((slots,), jnp.int32)
        self.max_new_arr = jnp.zeros((slots,), jnp.int32)

        self.live: List[Optional[Request]] = [None] * slots
        self._pos_host = [0] * slots          # mirror of self.pos for live
        self._admit_seq = [0] * slots         # admission order (preemption)
        self._admit_counter = 0

        # speculative decode: each step verifies spec_k drafted tokens
        # plus the current one in a single multi-token kernel sweep,
        # accepting a prefix by rejection sampling (exact-greedy matching
        # at temperature 0) + one bonus token. Drafts come from `drafter`
        # (runtime/drafter.py — e.g. DraftModelDrafter) or, when None,
        # the built-in host-side n-gram prompt lookup. spec_k = 0 is the
        # plain one-token-per-step path.
        self.spec_k = spec_k
        self.spec_ngram = spec_ngram
        self.drafter = drafter

        # telemetry (decode_steps / decoded_tokens / wall clocks /
        # first_token_at live in ServingMetricsMixin, shared with the
        # dense engine)
        self.prefill_traces = 0               # == number of length buckets
        self.prompt_tokens = 0                # logical prompt tokens admitted
        self.prefilled_tokens = 0             # tokens actually prefilled
        self.cow_copies = 0                   # device page copies (CoW)
        self.spec_drafted = 0                 # draft tokens proposed
        self.spec_accepted = 0                # draft tokens accepted
        self.spec_slot_steps = 0              # (live slot, verify step) pairs
        self.win_recycled_pages = 0           # window pages slid out + freed
        self.greedy_requests = 0              # requests by effective policy
        self.sampled_requests = 0
        self.greedy_tokens = 0                # emitted tokens by policy
        self.sampled_tokens = 0
        # retrace telemetry: incremented at TRACE time inside the step
        # programs (a python side effect runs once per compilation), so
        # a mixed greedy+sampled batch proves its one-trace contract by
        # these staying at 1 (tests/test_sampling.py)
        self.step_traces = 0
        self.spec_traces = 0

        self._step_fn = jax.jit(self._make_step())
        self._spec_fn = jax.jit(self._make_spec_step()) if spec_k else None
        self._select_fn = jax.jit(self._make_select()) \
            if (spec_k and self.has_state) else None
        self._prefill_fn = jax.jit(self._make_prefill())
        # a finished request's recurrent state is zeroed in place, so a
        # dead slot decodes from a clean state until the next admission
        # writes its own (inside the prefill program)
        self._state_reset_fn = jax.jit(
            lambda c, s: api.state_slot_reset(cfg, c, s),
            donate_argnums=0) if self.has_state else None
        self._prefill_shared_fn = jax.jit(self._make_prefill_shared())
        self._cow_fn = jax.jit(self._make_cow())
        # host-tier page IO: kind-filtered gather/scatter pairs (full-attn
        # pools and window pools move independently — a swap record holds
        # one blob per table) plus recurrent state-slot export/import
        if host_tier:
            full_kinds = set(api.PAGEABLE_KINDS)
            win_kinds = set(api.WINDOW_KINDS)
            self._gather_full = jax.jit(self._make_pool_gather(full_kinds))
            self._scatter_full = jax.jit(self._make_pool_scatter(full_kinds))
            self._gather_win = jax.jit(self._make_pool_gather(win_kinds))
            self._scatter_win = jax.jit(self._make_pool_scatter(win_kinds))
            if self.has_state:
                # the swap-out half of the carried-over PR 5 open: a
                # preempted hybrid request carries its recurrent state to
                # host RAM instead of rebuilding it by re-prefill
                self._state_export_fn = jax.jit(
                    lambda c, s: api.state_slot_export(cfg, c, s))
                self._state_import_fn = jax.jit(
                    lambda c, s, st: api.state_slot_import(cfg, c, s, st))
        self._seen_buckets: set = set()

    # -- jitted device programs -------------------------------------------
    #
    # TP boundary discipline: each traced program keeps its single jax.jit
    # wrapper, and INSIDE it exactly the model call + page-pool
    # scatter/gather is shard_map'd (`_wrap_sharded`). Sampling, the PRNG
    # key split and the slot-bookkeeping updates stay outside the manual
    # region but inside the jit — typed PRNG keys never cross the manual
    # boundary, replicated bookkeeping compiles as trivially-partitioned
    # ops, and the one-dispatch / one-host-sync-per-step contract is
    # untouched by sharding.

    def _wrap_sharded(self, fn, n_rep: int):
        """Wrap a ``(params, cache, *replicated) -> (out, new_cache)``
        model call in the plan's ONE manual boundary; identity when
        single-shard. ``n_rep`` counts the replicated operands after
        (params, cache)."""
        if self.tp is None:
            return fn
        rep = (P(),) * n_rep
        return self.tp.shard(
            fn,
            in_specs=(self._param_specs, self._cache_specs) + rep,
            out_specs=(P(), self._cache_specs))

    def _decode_call(self, expert_rows: bool = False):
        """The model call both decode-side programs share — the exact
        extent of the TP manual region for a decode step. Works for T=1
        rows (plain step) and T=spec_k+1 blocks (speculative verify): the
        per-shard flash-decode sweep sees its local KV-head slice of the
        pool and the GQA fold is untouched (kernels/paged_attention.py).
        ``expert_rows`` adds the routed rows of each held expert per
        layer (api.decode_step) as a third result."""
        cfg, rules, has_win = self.cfg, self._model_rules, self.has_win

        def call(params, cache, block_table, win_table, tok, pos):
            return api.decode_step(
                cfg, params, cache, tok, pos, rules=rules,
                block_table=block_table,
                win_block_table=win_table if has_win else None,
                expert_rows=expert_rows)

        return call

    def _make_step(self):
        cfg = self.cfg
        eos, max_len = self.eos_id, self.max_len
        decode = self._wrap_sharded(self._decode_call(self._routes), 4)

        def step(params, cache, block_table, win_table, cur_tok, pos, live,
                 gen, max_new, pol):
            # trace-time side effect: runs once per compilation, never at
            # execution — the retrace telemetry behind the one-trace-per-
            # policy-mix contract. Policies arrive as (slots,) operands
            # (`pol`), so greedy and sampled rows share this trace.
            # repro-lint: disable=retrace-hazard — counting traces IS the point
            self.step_traces += 1
            # a dead slot keeps its last request's position, but its
            # table is all scratch: decode it at position 0, so the
            # attention kernel streams one page for it, not that length
            logits, cache, *rows = decode(params, cache, block_table,
                                          win_table, cur_tok,
                                          jnp.where(live, pos, 0))
            toks = sample_rows(logits[..., : cfg.vocab], pol)
            livei = live.astype(jnp.int32)
            pos2 = pos + livei
            gen2 = gen + livei
            done = live & ((toks == eos) | (gen2 >= max_new)
                           | (pos2 >= max_len - 1))
            live2 = live & ~done
            cur2 = jnp.where(live[:, None], toks[:, None], cur_tok)
            return (cache, cur2, pos2, gen2, live2, done, toks, *rows)

        return step

    def _make_spec_step(self):
        """Speculative verify-step device program: scatter the whole (B, T)
        token block's KV into the pages and score every row in ONE causal
        page sweep (api.decode_step with T = spec_k + 1), returning per
        row a rejection-sampling accept bit for its drafted token and the
        token to emit if the step stops there — the step's only host
        sync. Acceptance is distribution-preserving (runtime/sampling.py:
        both drafters propose deterministically, so q is a point mass and
        ``u < p(draft)`` is the full accept rule; greedy rows reduce to
        exact argmax matching, bit-identical to the pre-ISSUE-9 engine).
        The emitted token for a verify row is a RESIDUAL sample — the
        policy distribution with the rejected draft's mass removed — and
        for the bonus row (nothing left to verify) a full sample; greedy
        rows emit the argmax either way. The prefix walk, rollback and
        finish bookkeeping stay host-side: the accepted length is
        data-dependent per request, exactly what a fixed-shape jitted
        program can't express without padding every outcome. On stacks
        with recurrent layers the returned cache carries CHECKPOINTED
        states — a T axis of per-row states — which ``_select_fn``
        collapses to each slot's accepted row. (The checkpointed leaves
        still match ``_cache_specs``: specs constrain only the dims they
        name, state slots are P() at any rank.)"""
        cfg = self.cfg
        decode = self._wrap_sharded(self._decode_call(), 4)

        def spec(params, cache, block_table, win_table, tok_block, pos,
                 n_draft, pol):
            # repro-lint: disable=retrace-hazard — counting traces IS the point
            self.spec_traces += 1     # trace-time retrace telemetry
            logits, cache = decode(params, cache, block_table, win_table,
                                   tok_block, pos)
            B, T = tok_block.shape
            z = logits[..., : cfg.vocab].astype(jnp.float32)
            V = z.shape[-1]
            z = z.reshape(B * T, V)

            def rep(a):               # (B,) slot operand -> (B*T,) rows
                return jnp.repeat(a, T)

            temp = rep(pol["temp"])
            z = scale_mask(z, temp, rep(pol["top_k"]), rep(pol["top_p"]))
            greedy = jnp.argmax(z, -1).astype(jnp.int32)
            # row t of slot s decides generated-token index idx[s] + t;
            # its key is the same fold the non-speculative step would use
            # for that position, so spec-off/spec-on agree wherever the
            # draw stream lines up (e.g. zero drafts, or temperature 0)
            t_off = jnp.tile(jnp.arange(T, dtype=jnp.int32), B)
            keys = fold_keys(rep(pol["seed"]), rep(pol["rid"]),
                             rep(pol["idx"]) + t_off)
            # the drafted token under test at verify row t is
            # tok_block[:, t + 1]; the last row has no draft (bonus row)
            draft = jnp.concatenate(
                [tok_block[:, 1:], jnp.zeros((B, 1), jnp.int32)],
                axis=1).reshape(-1)
            p_draft = jnp.take_along_axis(
                jax.nn.softmax(z, axis=-1), draft[:, None], axis=-1)[:, 0]
            u = jax.vmap(jax.random.uniform)(draw_keys(keys, ACCEPT_DRAW))
            accept = jnp.where(temp > 0, u < p_draft, greedy == draft)
            # emission token if the step stops at this row: residual
            # sample (draft's mass removed) on a rejected verify row,
            # full sample on the bonus row, argmax on greedy rows. One
            # noise draw serves both candidates — only one is consumed.
            is_verify = t_off < rep(n_draft)
            z_res = jnp.where(
                (jnp.arange(V)[None, :] == draft[:, None])
                & is_verify[:, None], NEG_FILTER, z)
            g = jax.vmap(lambda k: jax.random.gumbel(
                k, (V,), jnp.float32))(draw_keys(keys, SAMPLE_DRAW))
            noise = jnp.where(temp > 0, 1.0, 0.0)[:, None] * g
            full_tok = jnp.argmax(z + noise, -1).astype(jnp.int32)
            res_tok = jnp.argmax(z_res + noise, -1).astype(jnp.int32)
            emit = jnp.where(
                temp > 0, jnp.where(is_verify, res_tok, full_tok), greedy)
            return (cache, accept.reshape(B, T),
                    emit.astype(jnp.int32).reshape(B, T))

        return spec

    def _make_select(self):
        """Recurrent-state rollback for speculative decode: the verify
        step's T-step recurrence checkpointed the state after EVERY block
        row (ssm_decode / rglru_decode with T > 1); given each slot's
        accepted row index this gathers the state the T=1 engine would
        have reached — the state-slot analogue of the page rollback
        ``PageAllocator.truncate_to`` performs for KV."""
        kinds, tail = self._kinds, self._tail
        state = set(api.STATE_KINDS)

        def sel(cache, idx):          # idx: (slots,) accepted row per slot
            def g_tail(leaf):         # (B, T, ...) -> (B, ...)
                ix = idx.reshape((-1,) + (1,) * (leaf.ndim - 1))
                return jnp.take_along_axis(leaf, ix, axis=1)[:, 0]

            def g_scan(leaf):         # (L, B, T, ...) -> (L, B, ...)
                ix = idx.reshape((1, -1) + (1,) * (leaf.ndim - 2))
                return jnp.take_along_axis(
                    leaf, jnp.broadcast_to(
                        ix, (leaf.shape[0],) + ix.shape[1:]), axis=2)[:, :, 0]

            new_scan = {}
            for j, kd in enumerate(kinds):
                e = cache["scan"].get(str(j))
                if e is None:
                    continue
                new_scan[str(j)] = jax.tree.map(g_scan, e) \
                    if kd in state else e
            new_tail = [jax.tree.map(g_tail, e) if kd in state else e
                        for e, kd in zip(cache["tail"], tail)]
            return {"scan": new_scan, "tail": new_tail}

        return sel

    def _make_prefill(self):
        cfg = self.cfg
        rules = self._model_rules
        page = self.page_size
        kinds, tail = self._kinds, self._tail
        hybrid = self.has_win or self.has_state

        def model(params, cache, tokens, length, pages, pages_win, slot):
            # hybrid stacks prefill with paged_kv: recurrent state updates
            # are masked past `length` (bucket padding never leaks into
            # the state slot) and local_attn yields full-sequence kv for
            # the window-page scatter below
            logits, cache1, _ = api.prefill(cfg, params, {"tokens": tokens},
                                            rules=rules, length=length,
                                            paged_kv=hybrid)

            # scatter the prompt's kv blocks into the page pools: full-
            # attention layers through `pages`, sliding-window layers
            # through `pages_win` (whose below-window AND beyond-
            # allocation/bucket-padding blocks are SCRATCH_PAGE); write
            # recurrent layers' state into this request's slot.
            def merge_scan(pool, one, pg):      # (L,P,pg,..) <- (L,1,Sb,..)
                L = pool.shape[0]
                nb = one.shape[2] // page
                blocks = one.reshape((L, nb, page) + one.shape[3:])
                return pool.at[:, pg].set(blocks.astype(pool.dtype))

            def merge_tail(pool, one, pg):      # (P,pg,..) <- (1,Sb,..)
                nb = one.shape[1] // page
                blocks = one.reshape((nb, page) + one.shape[2:])
                return pool.at[pg].set(blocks.astype(pool.dtype))

            def state_scan(st, one):            # (L,slots,..) <- (L,1,..)
                return st.at[:, slot].set(one[:, 0].astype(st.dtype))

            def state_tail(st, one):            # (slots,..) <- (1,..)
                return st.at[slot].set(one[0].astype(st.dtype))

            def merged(kd, e, e1, scan_axis):
                if kd in api.STATE_KINDS:
                    return jax.tree.map(state_scan if scan_axis
                                        else state_tail, e, e1)
                pg = pages if kd in api.PAGEABLE_KINDS else pages_win
                mg = merge_scan if scan_axis else merge_tail
                return jax.tree.map(lambda p_, o, _pg=pg: mg(p_, o, _pg),
                                    e, e1)

            new_cache = {
                "scan": {str(j): merged(kd, cache["scan"][str(j)],
                                        cache1["scan"][str(j)], True)
                         for j, kd in enumerate(kinds)
                         if str(j) in cache["scan"]},
                "tail": [merged(kd, e, e1, False)
                         for kd, e, e1 in zip(tail, cache["tail"],
                                              cache1["tail"])],
            }
            return logits, new_cache

        model = self._wrap_sharded(model, 5)

        def pf(params, cache, block_table, win_table, pos, cur_tok, live,
               gen, max_new_arr, tokens, length, pages, pages_win, row,
               row_win, slot, req_max_new, pol):
            logits, new_cache = model(params, cache, tokens, length, pages,
                                      pages_win, slot)
            tok = sample_rows(logits[..., : cfg.vocab], pol)[0]
            block_table = block_table.at[slot].set(row)
            win_table = win_table.at[slot].set(row_win)
            pos = pos.at[slot].set(length)
            cur_tok = cur_tok.at[slot, 0].set(tok)
            live = live.at[slot].set(True)
            gen = gen.at[slot].set(1)
            max_new_arr = max_new_arr.at[slot].set(req_max_new)
            return (new_cache, block_table, win_table, pos, cur_tok, live,
                    gen, max_new_arr, tok)

        return pf

    def _make_prefill_shared(self):
        """Prefill a request whose first ``prefix_len`` tokens' KV already
        sits in the pool (prefix-cache hit): gather the matched pages into
        a per-layer prefix buffer, run the model over the SUFFIX only
        (api.prefill prefix_kv — the FLOPs saving the prefix cache exists
        for), and scatter the suffix k/v token-by-token into its pages
        (``phys_tok``/``row_tok``: physical page + row per suffix token,
        SCRATCH for bucket padding — token-granular because a CoW'd
        divergence can start mid-page)."""
        cfg = self.cfg
        rules = self._model_rules
        page = self.page_size

        def model(params, cache, tokens, length, prefix_pages, prefix_len,
                  phys_tok, row_tok):
            npb = prefix_pages.shape[0]

            def gather_scan(pool):          # (L,P,pg,..) -> (L,1,npb*pg,..)
                g = jnp.take(pool, prefix_pages, axis=1)
                return g.reshape((pool.shape[0], 1, npb * page)
                                 + pool.shape[3:])

            def gather_tail(pool):          # (P,pg,..) -> (1,npb*pg,..)
                g = jnp.take(pool, prefix_pages, axis=0)
                return g.reshape((1, npb * page) + pool.shape[2:])

            prefix_kv = {
                "scan": jax.tree.map(gather_scan, cache["scan"]),
                "tail": [jax.tree.map(gather_tail, cp)
                         for cp in cache["tail"]],
            }
            logits, cache1, _ = api.prefill(cfg, params, {"tokens": tokens},
                                            rules=rules, length=length,
                                            prefix_kv=prefix_kv,
                                            prefix_len=prefix_len)

            def merge_scan(pool, one):      # (L,P,pg,..) <- (L,1,Sb,..)
                return pool.at[:, phys_tok, row_tok].set(
                    one[:, 0].astype(pool.dtype))

            def merge_tail(pool, one):      # (P,pg,..) <- (1,Sb,..)
                return pool.at[phys_tok, row_tok].set(
                    one[0].astype(pool.dtype))

            new_cache = {
                "scan": jax.tree.map(merge_scan, cache["scan"],
                                     cache1["scan"]),
                "tail": [jax.tree.map(merge_tail, cp, c1)
                         for cp, c1 in zip(cache["tail"], cache1["tail"])],
            }
            return logits, new_cache

        model = self._wrap_sharded(model, 6)

        # its own program name, jit_pf_shared: the prefill readers match
        # the jit_pf prefix, and a trace tells the two paths apart
        def pf_shared(params, cache, block_table, pos, cur_tok, live, gen,
                      max_new_arr, tokens, length, prefix_pages, prefix_len,
                      phys_tok, row_tok, row, slot, req_max_new, pol):
            logits, new_cache = model(params, cache, tokens, length,
                                      prefix_pages, prefix_len, phys_tok,
                                      row_tok)
            tok = sample_rows(logits[..., : cfg.vocab], pol)[0]
            block_table = block_table.at[slot].set(row)
            pos = pos.at[slot].set(prefix_len + length)
            cur_tok = cur_tok.at[slot, 0].set(tok)
            live = live.at[slot].set(True)
            gen = gen.at[slot].set(1)
            max_new_arr = max_new_arr.at[slot].set(req_max_new)
            return (new_cache, block_table, pos, cur_tok, live, gen,
                    max_new_arr, tok)

        return pf_shared

    def _make_cow(self):
        """Device-side copy-on-write: duplicate one physical page (every
        page-pool layer) into a fresh private page, so a request can
        diverge inside a shared page without corrupting the other
        readers. Recurrent state entries are NOT pools — their leading
        axes are (slots, ...), not (pages, ...) — and pass through
        untouched (sharing is rejected for state-bearing stacks anyway;
        the per-kind dispatch keeps that a local fact, not a load-bearing
        one)."""
        kinds, tail = self._kinds, self._tail
        state = set(api.STATE_KINDS)

        def cow(cache, src, dst):
            def cp_scan(pool):              # (L, P, pg, ..)
                return pool.at[:, dst].set(pool[:, src])

            def cp_tail(pool):              # (P, pg, ..)
                return pool.at[dst].set(pool[src])

            new_scan = {}
            for j, kd in enumerate(kinds):
                e = cache["scan"].get(str(j))
                if e is None:
                    continue
                new_scan[str(j)] = e if kd in state \
                    else jax.tree.map(cp_scan, e)
            new_tail = [e if kd in state else jax.tree.map(cp_tail, e)
                        for e, kd in zip(cache["tail"], tail)]
            return {"scan": new_scan, "tail": new_tail}

        return cow

    def _make_pool_gather(self, kinds_ok: set):
        """Host-tier D2H staging: gather ``pages``'s rows out of every
        page-pool layer whose kind is in ``kinds_ok`` into a detached blob
        tree (dict-keyed tail so entry indices survive the round-trip).
        Pages are padded to a power of two with SCRATCH (bounds trace
        count; the padded rows carry scratch garbage and scatter back onto
        the scratch page). Dtypes pass through — int8 pools swap bitwise."""
        kinds, tail = self._kinds, self._tail

        def gather(cache, pages):
            def g_scan(leaf):           # (L,P,pg,..) -> (L,n,pg,..)
                return jnp.take(leaf, pages, axis=1)

            def g_tail(leaf):           # (P,pg,..) -> (n,pg,..)
                return jnp.take(leaf, pages, axis=0)

            return {
                "scan": {str(j): jax.tree.map(g_scan, cache["scan"][str(j)])
                         for j, kd in enumerate(kinds)
                         if kd in kinds_ok and str(j) in cache["scan"]},
                "tail": {str(i): jax.tree.map(g_tail, e)
                         for i, (e, kd) in enumerate(zip(cache["tail"],
                                                         tail))
                         if kd in kinds_ok},
            }

        return gather

    def _make_pool_scatter(self, kinds_ok: set):
        """Host-tier H2D landing: write a gathered blob back into fresh
        ``pages`` of every matching pool layer (the promote half of the
        demote/promote round trip). Layers outside ``kinds_ok`` pass
        through untouched."""
        kinds, tail = self._kinds, self._tail

        def scatter(cache, pages, blob):
            def s_scan(pool, b):        # (L,P,pg,..) <- (L,n,pg,..)
                return pool.at[:, pages].set(b.astype(pool.dtype))

            def s_tail(pool, b):        # (P,pg,..) <- (n,pg,..)
                return pool.at[pages].set(b.astype(pool.dtype))

            new_scan = {}
            for j, kd in enumerate(kinds):
                e = cache["scan"].get(str(j))
                if e is None:
                    continue
                new_scan[str(j)] = jax.tree.map(
                    s_scan, e, blob["scan"][str(j)]) \
                    if kd in kinds_ok else e
            new_tail = [jax.tree.map(s_tail, e, blob["tail"][str(i)])
                        if kd in kinds_ok else e
                        for i, (e, kd) in enumerate(zip(cache["tail"],
                                                        tail))]
            return {"scan": new_scan, "tail": new_tail}

        return scatter

    def _pad_pages(self, pages) -> jax.Array:
        """Page vector padded to a power of two with SCRATCH, so the
        gather/scatter programs trace once per size class, not once per
        page count (the prefill-bucket trick applied to swap IO)."""
        n = _next_pow2(max(1, len(pages)))
        out = np.full((n,), SCRATCH_PAGE, np.int32)
        out[: len(pages)] = pages
        return jnp.asarray(out)

    def _prefill_for(self, bucket) -> None:
        """jax.jit's shape cache gives one trace per bucket (plain bucket
        int for whole-prompt prefill, (suffix_bucket, prefix_pages) pairs
        for the shared path). The seen-bucket set drives the counter."""
        if bucket not in self._seen_buckets:
            self._seen_buckets.add(bucket)
            self.prefill_traces += 1

    # -- host-side engine -------------------------------------------------

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.live):
            if r is None:
                return i
        return None

    def _bucket(self, n: int) -> int:
        return min(max(self.page_size, _next_pow2(n)), self.max_len)

    def win_pages_bound(self, n_tokens: int) -> int:
        """Max simultaneous live window pages while serving ``n_tokens``:
        the window plus one in-flight write block (which is spec_k + 1
        tokens wide under speculative decode) can straddle
        ceil((window + T)/page) + 1 pages; fewer if the request never
        grows that long."""
        t_block = self.spec_k + 1
        return min(self.alloc.pages_for(n_tokens),
                   -(-(self.window + t_block) // self.page_size) + 1)

    def _worst_case_pages(self, n_tokens: int) -> int:
        """Pages a request can ever hold at once (admission feasibility)."""
        need = 0
        if self.has_full:
            need += self.alloc.pages_for(n_tokens)
        if self.has_win:
            need += self.win_pages_bound(n_tokens)
        return need

    def _submit(self, req: Request) -> bool:
        """Prefill `req` into a free slot. False if out of slots or pages
        (admission rejection — never corrupts a live neighbor's pages).

        With the prefix cache on, admission first takes the longest cached
        prefix (whole pages shared by refcount, plus at most one partial
        page duplicated copy-on-write), prefills only the remaining
        suffix, and afterwards publishes the request's own full prompt
        pages into the radix tree for the next arrival to reuse."""
        slot = self._free_slot()
        if slot is None:
            return False
        if self.tier is not None and self.tier.has_swap(req.rid):
            # swapped-out request: resume by promoting its pages + state
            # back from the host tier — no re-prefill. Runs BEFORE the
            # reject-as-done guard below on purpose: a request that was
            # live when preempted always satisfies it (pos <= max_len - 2,
            # generation budget left), and the guard's re-prefill footprint
            # math doesn't describe a swap-in.
            with self.trace.span("swap_in"):
                return self._swap_in(req, slot)
        toks = list(req.prompt) + list(req.generated)   # resume-on-preempt
        L = len(toks)
        remaining = req.max_new - len(req.generated)
        # decode stops at max_len-1 regardless of max_new, so the worst-
        # case footprint is bounded by max_len tokens (windowed tables by
        # O(window) pages — recycling keeps them there)
        worst = min(L + remaining, self.max_len)
        if (L >= self.max_len - 1 or remaining <= 0
                or self._worst_case_pages(worst) > self.alloc.num_pages):
            # can't (or needn't) ever serve this request: drop it as done
            # with whatever it has, rather than crash the loop or let the
            # scheduler retry an admission that can never succeed
            req.done = True
            self._note_finished(req.rid)
            return True

        shared: List[int] = []
        partial_page, partial_tokens = None, 0
        m = None
        if self.prefix is not None:
            # cap at L-1: at least one token must be prefilled — its logits
            # pick the next token, a pure cache hit has none to offer
            m = self.prefix.match(toks, max_tokens=L - 1)
            if self.tier is not None:
                # hits on host-resident radix nodes: promote them back to
                # device pages (H2D, prefetched a tick ahead when the
                # scheduler showed us this request) instead of letting the
                # match silently shrink to the device-resident prefix
                with self.trace.span("promote_match"):
                    m = self._promote_match(m)
            shared = m.pages
            partial_page, partial_tokens = m.partial_page, m.partial_tokens
        need_fresh = (self.alloc.pages_for(L) - len(shared)
                      if self.has_full else 0)
        deficit = need_fresh - self.alloc.free_pages
        if deficit > 0 and self.prefix is not None:
            # shed idle cached pages before rejecting admission — but
            # only if shedding can actually cover the deficit: flushing
            # still-matchable prefixes ahead of a rejection that happens
            # anyway would cost every future hit and buy nothing. The
            # match's own pages are not yet refcounted, so shield them.
            # With the host tier on, "shed" means demote (the node stays
            # matchable), and any idle node qualifies — not just leaves.
            keep = set(shared)
            if partial_page is not None:
                keep.add(partial_page)
            can = (self.prefix.demotable_count(keep) if self.tier is not None
                   else self.prefix.evictable_count(protect=keep))
            if can >= deficit:
                self._shed_idle_cache(deficit, protect=keep)
        table: List[int] = []
        if self.has_full:
            got = self.alloc.allocate_shared(req.rid, L, shared)
            if got is None:
                return False         # pool full: reject admission
            table = got
        wtable: List[int] = []
        dead0 = 0
        if self.has_win:
            # a prompt longer than the window admits with its pre-window
            # blocks never allocated (base_blocks): future queries sit at
            # positions >= L and can only see keys > L - window
            dead0 = min(max(0, L - self.window + 1) // self.page_size,
                        self.alloc.pages_for(L) - 1)
            got = self.alloc.allocate(_win_rid(req.rid), L,
                                      base_blocks=dead0)
            if got is None:
                if self.has_full:
                    self.alloc.free_request(req.rid)
                return False         # pool full: reject admission
            wtable = got
        if m is not None:
            # admission is now certain: count the lookup and touch the
            # matched path's LRU clock (a rejected-and-retried submit must
            # not inflate hit rates or keep its prefix hot)
            self.prefix.commit(m, L)
        prefix_len = len(shared) * self.page_size + partial_tokens
        if partial_page is not None:
            # the request diverges INSIDE a cached page: duplicate it into
            # the request's fresh page (rows < partial_tokens are reused,
            # the rest is overwritten by the suffix prefill below)
            dst = table[len(shared)]
            self.cache = self._cow_fn(self.cache, jnp.int32(partial_page),
                                      jnp.int32(dst))
            self.cow_copies += 1
            self.trace.instant("cow_copy", tid="prefix")

        row = np.zeros((self.max_blocks,), np.int32)
        row[: len(table)] = table
        # sliding-window device row: logical block j of [dead0, dead0+n)
        # holds wtable[j - dead0]; everything else (recycled lead blocks,
        # never-written tail) stays SCRATCH
        row_win = np.zeros((self.max_blocks,), np.int32)
        row_win[dead0: dead0 + len(wtable)] = wtable
        # the prefill's own draw decides generated-token index
        # len(req.generated) (> 0 on preemption-resume: the fold replays
        # the identical token the unpreempted run drew there)
        pol_req = request_params(req, self.default_params)
        pol = policy_operands([pol_req], [req.rid], [len(req.generated)],
                              self.seed)
        if prefix_len == 0:
            bucket = self._bucket(L)
            nb = bucket // self.page_size
            pages = np.full((nb,), SCRATCH_PAGE, np.int32)
            pages[: len(table)] = table[:nb]
            pages_win = np.full((nb,), SCRATCH_PAGE, np.int32)
            pages_win[dead0: min(dead0 + len(wtable), nb)] = \
                wtable[: max(0, nb - dead0)]
            tok_arr = np.zeros((1, bucket), np.int32)
            tok_arr[0, :L] = toks
            self._prefill_for(bucket)
            tr = self.trace
            with tr.span("prefill_dispatch",
                         args={"bucket": bucket} if tr else None), \
                    self._state_span("state_write", slot):
                (self.cache, self.block_table, self.win_table, self.pos,
                 self.cur_tok, self.live_mask, self.gen_cnt,
                 self.max_new_arr, tok) = self._prefill_fn(
                    self.params, self.cache, self.block_table,
                    self.win_table, self.pos, self.cur_tok, self.live_mask,
                    self.gen_cnt, self.max_new_arr, jnp.asarray(tok_arr),
                    jnp.int32(L), jnp.asarray(pages),
                    jnp.asarray(pages_win), jnp.asarray(row),
                    jnp.asarray(row_win), jnp.int32(slot),
                    jnp.int32(remaining), pol)
            self.prefilled_tokens += L
        else:
            suffix = toks[prefix_len:]
            bucket = self._bucket(len(suffix))
            # prefix pages to gather: the shared full pages plus the CoW'd
            # partial page, padded to a power of two (bounds trace count;
            # scratch-padded rows sit past every real position and are
            # causally masked)
            n_pref = len(shared) + (1 if partial_page is not None else 0)
            npb = min(_next_pow2(n_pref), self.max_blocks)
            pages = np.full((npb,), SCRATCH_PAGE, np.int32)
            pages[:n_pref] = table[:n_pref]
            # physical (page, row) of every suffix token; bucket padding
            # lands on the scratch page
            phys = np.full((bucket,), SCRATCH_PAGE, np.int32)
            rows = np.zeros((bucket,), np.int32)
            for t in range(bucket):
                ab = prefix_len + t
                rows[t] = ab % self.page_size
                if ab < L:
                    phys[t] = table[ab // self.page_size]
            tok_arr = np.zeros((1, bucket), np.int32)
            tok_arr[0, : len(suffix)] = suffix
            self._prefill_for(("shared", bucket, npb))
            tr = self.trace
            with tr.span("prefill_dispatch",
                         args={"bucket": bucket, "shared": prefix_len}
                         if tr else None):
                (self.cache, self.block_table, self.pos, self.cur_tok,
                 self.live_mask, self.gen_cnt, self.max_new_arr,
                 tok) = self._prefill_shared_fn(
                    self.params, self.cache, self.block_table, self.pos,
                    self.cur_tok, self.live_mask, self.gen_cnt,
                    self.max_new_arr, jnp.asarray(tok_arr),
                    jnp.int32(len(suffix)), jnp.asarray(pages),
                    jnp.int32(prefix_len), jnp.asarray(phys),
                    jnp.asarray(rows), jnp.asarray(row), jnp.int32(slot),
                    jnp.int32(remaining), pol)
            self.prefilled_tokens += len(suffix)
        self.prompt_tokens += L
        if self.prefix is not None:
            # publish the prompt's full pages for future arrivals (before
            # the finish check: even a request that completes at prefill
            # seeds the cache — its pages survive via the tree's pin)
            self.prefix.insert(toks, table)

        self.live[slot] = req
        self._pos_host[slot] = L
        self._policy[slot] = pol_req
        self._rid_host[slot] = req.rid
        self._admit_counter += 1
        self._admit_seq[slot] = self._admit_counter
        with self.trace.span("prefill_sync"):
            t = int(tok)
        first = req.rid not in self.first_token_at
        req.generated.append(t)
        self._samp_idx[slot] = len(req.generated)
        self._note_emitted(req.rid)
        if first:
            if pol_req.is_greedy:
                self.greedy_requests += 1
            else:
                self.sampled_requests += 1
        self._count_tokens(pol_req, 1)
        if (t == self.eos_id or len(req.generated) >= req.max_new):
            self._finish_slot(slot)
        return True

    def _release_slot(self, slot: int) -> Request:
        """Reclaim a slot's pages; the slot's table becomes all-scratch so
        a dead slot can only ever write to the scratch page."""
        req = self.live[slot]
        self.live[slot] = None
        self._policy[slot] = None
        if self.has_full:
            self.alloc.free_request(req.rid)
            self.block_table = self.block_table.at[slot].set(SCRATCH_PAGE)
        if self.has_win:
            self.alloc.free_request(_win_rid(req.rid))
            self.win_table = self.win_table.at[slot].set(SCRATCH_PAGE)
        self.live_mask = self.live_mask.at[slot].set(False)
        if self.drafter is not None:
            # the drafter's private context cache for this request is
            # stale the moment the slot releases (finish or preemption —
            # a resumed request re-ingests)
            self.drafter.drop(req.rid)
        return req

    def _finish_slot(self, slot: int) -> None:
        req = self._release_slot(slot)
        if self._state_reset_fn is not None:
            with self._state_span("state_reset", slot):
                self.cache = self._state_reset_fn(self.cache,
                                                  jnp.int32(slot))
        req.done = True
        self._note_finished(req.rid)

    def _state_span(self, name: str, slot: int):
        """A span around the program that writes (``state_write``: the
        prefill) or zeroes (``state_reset``) a slot's recurrent state;
        none on stacks without recurrent layers."""
        tr = self.trace
        if not (tr and self.has_state):
            return NOOP_SPAN
        return tr.span(name, args={"slot": slot})

    def _evict_slot(self, slot: int) -> Request:
        """Preempt destructively: reclaim pages, return the request for
        re-admission (it resumes by re-prefilling prompt +
        generated-so-far). With the host tier on, ``_swap_out_slot`` is
        the preferred path — this survives as its overflow fallback."""
        req = self._release_slot(slot)
        req.preemptions += 1
        return req

    def _reclaim_one_page(self, keep_slot: int,
                          preempted: List[Request]) -> bool:
        """Free at least one page for `keep_slot`: first shed an idle
        cached page (demote-or-evict — costs at most one future promote
        or re-prefill), only then preempt the youngest other live request
        (swap-out when the tier is on; destructive re-prefill preemption
        otherwise). False if neither source has anything left."""
        if self._shed_idle_cache(1):
            return True
        victims = [s for s, r in enumerate(self.live)
                   if r is not None and s != keep_slot]
        if not victims:
            return False
        youngest = max(victims, key=lambda s: self._admit_seq[s])
        if self.tier is not None:
            with self.trace.span("swap_out"):
                preempted.append(self._swap_out_slot(youngest))
        else:
            self.trace.instant("preempt")
            preempted.append(self._evict_slot(youngest))
        return True

    # -- host tier: demote / promote / swap --------------------------------

    def _shed_idle_cache(self, n_pages: int,
                         protect: Optional[set] = None) -> int:
        """Free ``n_pages`` device pages from the idle prefix cache. Tier
        off: plain LRU eviction. Tier on: demote first — gather the page's
        KV (one-page blobs: the page-granular half of the mixed-grained
        streamer), hand it to the host store and free the device page
        while the node stays matchable — falling back to eviction only
        when the host store refuses (capacity cap). Returns pages freed."""
        if self.prefix is None:
            return 0
        if self.tier is None:
            return self.prefix.evict(n_pages, protect=protect)
        freed = 0
        for node in self.prefix.demotable(protect):
            if freed >= n_pages:
                break
            blob = self._gather_full(self.cache,
                                     self._pad_pages([node.page]))
            if not self.tier.can_accept(_tree_nbytes(blob)):
                break                # host store full: evict the rest
            handle = self.tier.store.put(blob)
            self.prefix.demote_node(node, handle)
            self.tier.cache_demotions += 1
            self.tier.demoted_pages += 1
            freed += 1
        if freed < n_pages:
            freed += self.prefix.evict(n_pages - freed, protect=protect)
        return freed

    def _promote_match(self, m: PrefixMatch) -> PrefixMatch:
        """Promote every host-resident node on a match's path back to a
        fresh pinned device page (H2D through the copy stream — a hit
        when the scheduler's prefetch hook saw this prompt last tick).
        If the pool can't supply a page mid-path, the match truncates at
        that node: the pages BELOW the cut are already promoted and
        usable, everything above re-prefills."""
        for i, node in enumerate(m.path):
            if node.page is not None:
                m.pages[i] = node.page     # promoted by an earlier caller
                continue
            page = self.alloc.alloc_pinned_page()
            if page is None:
                return PrefixMatch(
                    m.pages[:i], i * self.page_size,
                    node=m.path[i - 1] if i else None, path=m.path[:i])
            handle = node.host
            blob = self.tier.stream.take(handle)
            self.cache = self._scatter_full(self.cache,
                                            self._pad_pages([page]), blob)
            self.tier.store.pop(handle)
            self.prefix.promote_node(node, page)
            self.tier.cache_promotions += 1
            self.tier.promoted_pages += 1
            m.pages[i] = page
        return m

    def _swap_out_slot(self, slot: int) -> Request:
        """Preempt WITHOUT destroying work: gather the slot's full and
        window tables into host blobs (request-granular), export its
        recurrent state slots, then demote the allocator bookkeeping and
        free the device pages — gather-then-free is safe under JAX
        dispatch ordering. Falls back to destructive eviction when the
        host store refuses the bytes (counted, loud)."""
        req = self.live[slot]
        tier = self.tier
        rec = SwapRecord(rid=req.rid, pos=self._pos_host[slot])
        blobs = {}
        if self.has_full:
            table = self.alloc.block_table(req.rid)
            rec.full_pages = len(table)
            blobs["full"] = self._gather_full(self.cache,
                                              self._pad_pages(table))
        if self.has_win:
            wrid = _win_rid(req.rid)
            wtable = self.alloc.block_table(wrid)
            rec.win_pages = len(wtable)
            rec.win_base = self.alloc.base_blocks(wrid)
            blobs["win"] = self._gather_win(self.cache,
                                            self._pad_pages(wtable))
        if self.has_state:
            blobs["state"] = self._state_export_fn(self.cache,
                                                   jnp.int32(slot))
        if not tier.can_accept(sum(_tree_nbytes(b) for b in blobs.values())):
            return self._evict_slot(slot)
        for name, blob in blobs.items():
            setattr(rec, name, tier.store.put(blob))
        if self.has_full:
            self.alloc.demote(req.rid)
            self.block_table = self.block_table.at[slot].set(SCRATCH_PAGE)
        if self.has_win:
            self.alloc.demote(_win_rid(req.rid))
            self.win_table = self.win_table.at[slot].set(SCRATCH_PAGE)
        tier.demoted_pages += rec.full_pages + rec.win_pages
        self.live[slot] = None
        self._policy[slot] = None
        self.live_mask = self.live_mask.at[slot].set(False)
        if self.drafter is not None:
            self.drafter.drop(req.rid)
        tier.record_swap(rec)
        req.preemptions += 1
        return req

    def _swap_in(self, req: Request, slot: int) -> bool:
        """Resume a swapped-out request: promote its allocator tables,
        scatter the host blobs into the fresh pages (the copy stream
        already has them in flight when the prefetch hook fired), import
        its recurrent state, and rebuild the slot bookkeeping exactly
        where the preemption left it — pos, current token, remaining
        generation budget. NO tokens are prefilled and none are emitted.
        False (request keeps waiting) if the pool can't host it yet —
        a swap-in never preempts someone else (anti-thrash)."""
        tier = self.tier
        rec = tier.peek_swap(req.rid)
        need = 0
        if self.has_full:
            need += self.alloc.host_pages_needed(req.rid)
        if self.has_win:
            need += self.alloc.host_pages_needed(_win_rid(req.rid))
        deficit = need - self.alloc.free_pages
        if deficit > 0:
            self._shed_idle_cache(deficit)
            if need > self.alloc.free_pages:
                return False
        table: List[int] = []
        if self.has_full:
            table = self.alloc.promote(req.rid)
            assert table is not None
        wtable: List[int] = []
        if self.has_win:
            wtable = self.alloc.promote(_win_rid(req.rid))
            assert wtable is not None
        if rec.full is not None:
            blob = tier.stream.take(rec.full)
            self.cache = self._scatter_full(self.cache,
                                            self._pad_pages(table), blob)
        if rec.win is not None:
            blob = tier.stream.take(rec.win)
            self.cache = self._scatter_win(self.cache,
                                           self._pad_pages(wtable), blob)
        if rec.state is not None:
            self.cache = self._state_import_fn(
                self.cache, jnp.int32(slot), tier.stream.take(rec.state))
        tier.promoted_pages += len(table) + len(wtable)
        row = np.zeros((self.max_blocks,), np.int32)
        row[: len(table)] = table
        self.block_table = self.block_table.at[slot].set(jnp.asarray(row))
        row_win = np.zeros((self.max_blocks,), np.int32)
        row_win[rec.win_base: rec.win_base + len(wtable)] = wtable
        self.win_table = self.win_table.at[slot].set(jnp.asarray(row_win))
        self.pos = self.pos.at[slot].set(rec.pos)
        self.cur_tok = self.cur_tok.at[slot, 0].set(int(req.generated[-1]))
        self.live_mask = self.live_mask.at[slot].set(True)
        # gen restarts at 1 with a rebased budget, exactly the re-prefill
        # resume's accounting: done when total generated reaches max_new
        self.gen_cnt = self.gen_cnt.at[slot].set(1)
        self.max_new_arr = self.max_new_arr.at[slot].set(
            req.max_new - len(req.generated) + 1)
        self.live[slot] = req
        self._pos_host[slot] = rec.pos
        self._policy[slot] = request_params(req, self.default_params)
        self._rid_host[slot] = req.rid
        # the next draw decides generated-token index len(generated) —
        # the same fold the unpreempted run would have used there
        self._samp_idx[slot] = len(req.generated)
        self._admit_counter += 1
        self._admit_seq[slot] = self._admit_counter
        tier.pop_swap(req.rid)
        return True

    def prefetch_pending(self, pending: List[Request]) -> None:
        """The streamer's look-ahead (Scheduler.tick calls this with the
        queue snapshot between admission and decode): start the H2D
        copies that NEXT tick's admissions will consume — request-
        granular for swapped-out requests (their whole swap set), page-
        granular for prompts whose radix match crosses host-resident
        nodes — so they overlap this tick's decode step."""
        if self.tier is None:
            return
        for req in pending:
            if self.tier.has_swap(req.rid):
                for h in self.tier.peek_swap(req.rid).handles():
                    self.tier.stream.prefetch(h)
            elif self.prefix is not None:
                toks = list(req.prompt) + list(req.generated)
                m = self.prefix.match(toks, max_tokens=len(toks) - 1)
                for node in m.path:
                    if node.page is None:
                        self.tier.stream.prefetch(node.host)

    def tier_stats(self) -> Dict[str, float]:
        """Host-tier telemetry. The key set is identical whether the tier
        is on or off (``HostTier.zero_stats`` fills zeros) so downstream
        CSV columns never shift with configuration."""
        d: Dict[str, float] = {"host_tier": float(self.tier is not None)}
        d.update(self.tier.stats() if self.tier is not None
                 else HostTier.zero_stats())
        return d

    def ensure_decode_capacity(self, n_tokens: int = 1) -> List[Request]:
        """Allocate the pages the next decode step will write into
        (allocate-on-demand). ``n_tokens`` > 1 provisions a speculative
        verify block's WHOLE write range — positions pos .. pos+n_tokens-1,
        capped at max_len — so a multi-token step can never write an
        unallocated page (rows past max_len are redirected to scratch by
        the model layer and their logits discarded by the max_len stop).
        On pool exhaustion, evict idle prefix-cache pages first, then
        preempt the youngest live requests until the remaining ones fit.
        Returns preempted requests (resubmit them to resume). Also
        enforces the write-exclusivity invariant over the whole write
        range: every page the step may write must be privately owned — if
        one is shared (refcount > 1: another table or the radix tree
        references it), it is duplicated copy-on-write first."""
        preempted: List[Request] = []
        page = self.page_size
        for slot in sorted((s for s, r in enumerate(self.live)
                            if r is not None),
                           key=lambda s: self._admit_seq[s]):
            req = self.live[slot]
            if req is None:
                continue
            pos = self._pos_host[slot]
            target = min(pos + n_tokens, self.max_len)
            if self.has_win:
                # recycle window pages FIRST: blocks entirely below every
                # future query's window (< pos - window + 1) free pages
                # this very top-up may need — that recycling is what
                # bounds a windowed request at O(window) live pages
                self._recycle_win(slot, req.rid, pos)
                self._grow_table(_win_rid(req.rid), slot, target,
                                 preempted, win=True)
                self._recycle_win(slot, req.rid, pos)
            if self.has_full:
                self._grow_table(req.rid, slot, target, preempted,
                                 win=False)
                # write exclusivity across every block the step may touch
                # (only the first — the partially-written one — can
                # actually be shared; the loop is the defensive spelling;
                # window pages are never shared, so full tables only)
                for blk in range(pos // page, (target - 1) // page + 1):
                    while self.alloc.ref(
                            self.alloc.block_table(req.rid)[blk]) > 1:
                        swapped = self.alloc.replace_page(req.rid, blk)
                        if swapped is not None:
                            src, dst = swapped
                            self.cache = self._cow_fn(self.cache,
                                                      jnp.int32(src),
                                                      jnp.int32(dst))
                            self.block_table = self.block_table.at[
                                slot, blk].set(dst)
                            self.cow_copies += 1
                            break
                        if not self._reclaim_one_page(slot, preempted):
                            raise RuntimeError(
                                "page pool too small for a single request")
        return preempted

    def _grow_table(self, rid, slot: int, target: int,
                    preempted: List[Request], *, win: bool) -> None:
        """Grow ``rid``'s table page-by-page until it covers ``target``
        tokens (extend_to grows at most one page per call), publishing
        fresh pages to the matching device table and reclaiming (evict /
        preempt-youngest) on pool exhaustion."""
        page = self.page_size
        while True:
            have = (self.alloc.base_blocks(rid)
                    + len(self.alloc.block_table(rid))) * page
            got = self.alloc.extend_to(rid, min(target, have + page))
            if got is None:
                if not self._reclaim_one_page(slot, preempted):
                    raise RuntimeError(
                        "page pool too small for a single request")
                continue
            if got:              # fresh page: publish to device table
                if win:
                    self.win_table = self.win_table.at[
                        slot, have // page].set(got)
                else:
                    self.block_table = self.block_table.at[
                        slot, have // page].set(got)
            if have + page >= target or not got:
                break

    def _recycle_win(self, slot: int, rid: int, pos: int) -> None:
        """Free this slot's window pages that slid entirely below the
        attention window: every query from here on sits at a position
        >= ``pos``, so keys at positions <= pos - window can never be
        read again. Their logical blocks go back to SCRATCH on device
        (the kernel skips them) and their pages back to the free list
        (PageAllocator.release_prefix). At least one block always stays
        (the one being written)."""
        wrid = _win_rid(rid)
        dead = max(0, pos - self.window + 1) // self.page_size
        base = self.alloc.base_blocks(wrid)
        n = min(dead - base, len(self.alloc.block_table(wrid)) - 1)
        if n > 0:
            if self.tier is not None:
                # demotion source 3: archive the slid-out blocks (capped)
                # before recycling — raw material for hybrid prefix
                # caching (ROADMAP open 5), gathered while the pages are
                # still live, freed right after (dispatch-order safe)
                pages = self.alloc.block_table(wrid)[:n]
                blob = self._gather_win(self.cache, self._pad_pages(pages))
                if self.tier.can_accept(_tree_nbytes(blob)):
                    self.tier.archive_window(rid, base, n,
                                             self.tier.store.put(blob))
            self.win_recycled_pages += self.alloc.release_prefix(wrid, n)
            self.win_table = self.win_table.at[
                slot, base:base + n].set(SCRATCH_PAGE)

    def _step_operands(self):
        """The plain decode program's operands at the current state."""
        pol = policy_operands(self._policy, self._rid_host,
                              self._samp_idx, self.seed)
        return (self.params, self.cache, self.block_table, self.win_table,
                self.cur_tok, self.pos, self.live_mask, self.gen_cnt,
                self.max_new_arr, pol)

    def lower_step(self):
        """The plain decode program lowered at the current state: its text
        shows what runs on the device each decode tick."""
        return self._step_fn.lower(*self._step_operands())

    def _step(self) -> List[Request]:
        """Advance every live slot: one device program, one host sync.
        With spec_k > 0 this is a speculative verify step emitting up to
        spec_k + 1 tokens per request; otherwise the plain one-token step.
        Tops up the pages the step will write into first (a bare
        submit/step loop must never cross a page boundary unallocated —
        that write would land on the scratch page and silently corrupt
        the request); returns any requests preempted by that top-up, for
        the caller to resubmit. (Callers use ``step()`` — the mixin's
        timed wrapper.)"""
        tr = self.trace
        if self.tier is not None:
            # the copy-stream contract's visibility point: pending D2H
            # copies finalize exactly once per decode tick
            with tr.span("tier_drain"):
                self.tier.drain()
        if self.spec_k:
            return self._step_speculative()
        if not any(r is not None for r in self.live):
            return []
        with tr.span("ensure_capacity"):
            evicted = self.ensure_decode_capacity()
        with tr.span("operands"):
            operands = self._step_operands()
        t0 = time.perf_counter()
        with tr.span("device_dispatch"):
            (self.cache, self.cur_tok, self.pos, self.gen_cnt,
             self.live_mask, done_d, toks_d, *rows_d) = self._step_fn(
                *operands)
        with tr.span("host_sync"):
            # repro-lint: disable=host-sync — THE one blessed sync per step
            toks, done, rows = jax.device_get(
                (toks_d, done_d, rows_d if tr else []))
        self.step_wall_s += time.perf_counter() - t0
        self.decode_steps += 1
        with tr.span("emit"):
            live, series = 0, {}
            if tr and self._kv_block_tokens:
                # blocks each layer's kernel streamed: cdiv(pos + 1, block)
                # per live slot, at the positions the step ran
                series["kv_blocks"] = float(sum(
                    -(-(self._pos_host[i] + 1) // self._kv_block_tokens)
                    for i, r in enumerate(self.live) if r is not None))
            for i, r in enumerate(self.live):
                if r is None:
                    continue
                live += 1
                r.generated.append(int(toks[i]))
                self._pos_host[i] += 1
                self._samp_idx[i] += 1
                self.decoded_tokens += 1
                self._count_tokens(self._policy[i], 1)
                self._note_emitted(r.rid)
                if done[i]:
                    self._finish_slot(i)
            if tr:
                tr.counter("pool_pages", {
                    "allocated": float(self.alloc.allocated_pages),
                    "free": float(self.alloc.free_pages),
                    "live": float(live), **series})
                if rows:
                    tr.counter("experts", expert_series(rows[0]))
        return evicted

    def _step_speculative(self) -> List[Request]:
        """One speculative verify step. Per live slot: draft up to spec_k
        tokens (the configured ``drafter``'s model, or prompt lookup over
        the request's OWN context — host-side, no second model), score
        [current token, drafts...] as a T = spec_k + 1 row block in one
        multi-token page sweep, rejection-sample the drafts against the
        slot's decode policy (row t accepts its draft w.p.
        ``min(1, p(draft)/q(draft))`` — ``u < p(draft)`` for our
        deterministic drafters; exact prefix match at temperature 0),
        emit the accepted prefix plus one more token (the residual sample
        after the first rejection, or a full bonus sample after row
        n_draft — so even an all-miss step emits exactly the plain step's
        token, and marginally every emitted token is distributed as a
        non-speculative sample; see runtime/sampling.py), then roll
        position and pages back past the accept point (truncate_to: whole
        pages the rejected rows provisioned are disowned; rejected rows
        inside a kept page are dead rows masked by the request length and
        overwritten by the next step). At temperature 0 this is the exact
        greedy verification it generalizes: outputs equal the T=1
        engine's token-for-token."""
        if not any(r is not None for r in self.live):
            return []
        tr = self.trace
        T = self.spec_k + 1
        with tr.span("ensure_capacity"):
            evicted = self.ensure_decode_capacity(T)
        t0 = time.perf_counter()
        tok_block = np.zeros((self.slots, T), np.int32)
        n_draft = [0] * self.slots
        with tr.span("draft"):
            for s, r in enumerate(self.live):
                if r is None:
                    continue
                ctx = r.prompt + r.generated
                tok_block[s, 0] = ctx[-1]  # current token, not yet in cache
                if self.drafter is not None:
                    d = self.drafter.propose(r.rid, ctx,
                                             self.spec_k)[: self.spec_k]
                else:
                    d = ngram_propose(ctx, self.spec_k,
                                      max_ngram=self.spec_ngram)
                tok_block[s, 1:1 + len(d)] = d
                n_draft[s] = len(d)
                self.spec_drafted += len(d)
                self.spec_slot_steps += 1
        with tr.span("operands"):
            pol = policy_operands(self._policy, self._rid_host,
                                  self._samp_idx, self.seed)
            tok_block_d = jnp.asarray(tok_block)
            pos_d = jnp.asarray([p if r is not None else 0 for p, r in
                                 zip(self._pos_host, self.live)], jnp.int32)
            n_draft_d = jnp.asarray(n_draft, jnp.int32)
        with tr.span("device_dispatch"):
            self.cache, acc_d, emit_d = self._spec_fn(
                self.params, self.cache, self.block_table, self.win_table,
                tok_block_d, pos_d, n_draft_d, pol)
        with tr.span("host_sync"):
            # repro-lint: disable=host-sync — the verify step's one sync
            accept, emit = jax.device_get((acc_d, emit_d))  # 1 host sync
        self.step_wall_s += time.perf_counter() - t0
        self.decode_steps += 1
        with tr.span("accept_rollback"):
            survivors = []        # (slot, new_pos, emitted, cur_tok) rows
            accept_idx = np.zeros((self.slots,), np.int32)
            for s, r in enumerate(self.live):
                if r is None:
                    continue
                pos0 = self._pos_host[s]
                a = 0                      # accepted drafts
                while a < n_draft[s] and accept[s, a]:
                    a += 1
                # accepted drafts verbatim, then row a's sample (residual
                # after a rejection, full after the last accepted draft)
                emitted = [int(tok_block[s, j + 1]) for j in range(a)]
                emitted.append(int(emit[s, a]))
                # emit, applying the T=1 stop conditions in emission
                # order (eos / generation budget / context cap) — tokens
                # past the first stop are discarded, exactly as the plain
                # engine would never have produced them
                finished = False
                m = 0
                for j, t in enumerate(emitted):
                    r.generated.append(t)
                    m += 1
                    self.decoded_tokens += 1
                    if (t == self.eos_id or len(r.generated) >= r.max_new
                            or pos0 + j + 1 >= self.max_len - 1):
                        finished = True
                        break
                self.spec_accepted += m - 1
                accept_idx[s] = m - 1      # recurrent state after row m-1
                self._samp_idx[s] += m
                self._count_tokens(self._policy[s], m)
                self._note_emitted(r.rid, m)
                if finished:
                    self._finish_slot(s)   # frees every page incl. drafts
                    continue
                # rollback: disown the whole pages past the accept point
                # and republish their table slots as scratch on device —
                # full and window tables alike (a rejected row may have
                # crossed a page boundary in either)
                if self.has_full:
                    dropped = self.alloc.truncate_to(r.rid, pos0 + m)
                    if dropped:
                        keep = len(self.alloc.block_table(r.rid))
                        self.block_table = self.block_table.at[
                            s, keep:keep + dropped].set(SCRATCH_PAGE)
                if self.has_win:
                    wrid = _win_rid(r.rid)
                    dropped = self.alloc.truncate_to(wrid, pos0 + m)
                    if dropped:
                        keep = (self.alloc.base_blocks(wrid)
                                + len(self.alloc.block_table(wrid)))
                        self.win_table = self.win_table.at[
                            s, keep:keep + dropped].set(SCRATCH_PAGE)
                self._pos_host[s] = pos0 + m
                survivors.append((s, pos0 + m, m, int(r.generated[-1])))
            if self._select_fn is not None:
                # collapse the verify step's checkpointed recurrent states
                # (T axis) to each slot's accepted row — the state-slot
                # analogue of the page rollback above. Must run even when
                # every slot finished: the next step's trace expects plain
                # state shapes.
                self.cache = self._select_fn(self.cache,
                                             jnp.asarray(accept_idx))
            if survivors:
                # device mirrors (pos / gen / cur_tok) stay in sync — so
                # telemetry and a switch back to the T=1 path keep working
                # — via ONE batched update per array per step, not one
                # dispatch per slot
                idx = np.array([u[0] for u in survivors])
                self.pos = self.pos.at[idx].set(
                    np.array([u[1] for u in survivors], np.int32))
                self.gen_cnt = self.gen_cnt.at[idx].add(
                    np.array([u[2] for u in survivors], np.int32))
                self.cur_tok = self.cur_tok.at[idx, 0].set(
                    np.array([u[3] for u in survivors], np.int32))
        return evicted

    def spec_stats(self) -> Dict[str, float]:
        """Speculative-decode telemetry: draft volume, acceptance rate,
        and the headline number — tokens emitted per request per verify
        step (the plain engine is 1.0 per request-step by construction;
        the gap above 1.0 is decode wall-clock won at unchanged per-step
        page traffic)."""
        return {
            "spec_k": float(self.spec_k),
            "spec_drafted": float(self.spec_drafted),
            "spec_accepted": float(self.spec_accepted),
            "accept_rate": (self.spec_accepted / self.spec_drafted
                            if self.spec_drafted else 0.0),
            "accepted_per_step": (self.decoded_tokens / self.spec_slot_steps
                                  if self.spec_slot_steps else 1.0),
            "drafter": ("none" if not self.spec_k
                        else (self.drafter.kind if self.drafter is not None
                              else "ngram")),
        }

    def sampling_stats(self) -> Dict[str, float]:
        """Decode-policy telemetry (ISSUE 9): the greedy/sampled request
        and token mix, the jit trace counts the mixed-batch acceptance
        criterion asserts on (``step_traces`` / ``spec_traces`` — like
        ``prefill_traces`` these are lifetime facts that survive
        ``reset_metrics``), and the draft-model drafter's counters
        (zeros when no model drafter is attached, so the key set is
        engine- and configuration-stable)."""
        d = {
            "greedy_requests": float(self.greedy_requests),
            "sampled_requests": float(self.sampled_requests),
            "greedy_tokens": float(self.greedy_tokens),
            "sampled_tokens": float(self.sampled_tokens),
            "step_traces": float(self.step_traces),
            "spec_traces": float(self.spec_traces),
            "draft_proposed": 0.0,
            "draft_ingested_tokens": 0.0,
            "draft_decode_calls": 0.0,
            "draft_pool_rejects": 0.0,
        }
        if self.drafter is not None:
            d.update(self.drafter.stats())
        return d

    def has_live(self) -> bool:
        return any(r is not None for r in self.live)

    def pool_stats(self) -> PoolStats:
        return PoolStats.of(self.alloc, self.slots, self.max_len)

    def shard_stats(self) -> Dict[str, float]:
        """Per-shard telemetry for the TP engine (meaningful, if boring,
        on the single-shard engine too). Pages are allocated logically —
        host-side, shard-agnostic — and the block table is replicated, so
        every shard holds the SAME page set; what tensor parallelism
        divides is each page's bytes (a shard owns its KV-head slice of
        every page). ``peak_pages_per_shard`` is therefore the allocator's
        peak, and the per-shard byte number is what shrinks with M."""
        m = self.tp.model_shards if self.tp is not None else 1
        sharded_axes = sorted(self.tp.sharded_axes) if self.tp else []
        spec_leaves = None
        if self.tp is not None:
            spec_leaves = jax.tree.leaves(
                self._cache_specs, is_leaf=lambda x: isinstance(x, P))
        per_shard = 0
        for i, leaf in enumerate(jax.tree.leaves(self.cache)):
            nbytes = leaf.size * leaf.dtype.itemsize
            if spec_leaves is not None and _spec_uses(spec_leaves[i],
                                                      "model"):
                nbytes //= m
            per_shard += nbytes
        return {
            "model_shards": float(m),
            # "+"-joined, not ","-joined: this string lands in CSV cells
            "sharded_axes": "+".join(sharded_axes),
            "peak_pages_per_shard": float(self.alloc.peak_pages),
            "pool_bytes_per_shard": float(per_shard),
        }

    def prefix_stats(self) -> Dict[str, float]:
        """Prefix-sharing telemetry: token-level hit rate, prefill compute
        avoided, CoW traffic. Meaningful (non-zero) only with the prefix
        cache enabled; the prefill counters are kept either way so the
        no-sharing engine reports a comparable baseline."""
        d = {
            "prompt_tokens": float(self.prompt_tokens),
            "prefilled_tokens": float(self.prefilled_tokens),
            "prefill_tokens_saved": float(self.prompt_tokens
                                          - self.prefilled_tokens),
            "prefill_saved_frac": ((self.prompt_tokens
                                    - self.prefilled_tokens)
                                   / self.prompt_tokens
                                   if self.prompt_tokens else 0.0),
            "cow_copies": float(self.cow_copies),
        }
        d.update(self.prefix.stats() if self.prefix is not None
                 else PrefixCache.zero_stats())
        return d

    def _reset_subsystem_counters(self) -> None:
        """reset_metrics() tail: zero the paged engine's own telemetry and
        every enabled subsystem's counters (allocator peaks rebase to the
        current allocation; radix/tier contents survive — only rates
        reset)."""
        self.prompt_tokens = 0
        self.prefilled_tokens = 0
        self.cow_copies = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_slot_steps = 0
        self.win_recycled_pages = 0
        # step_traces / spec_traces deliberately survive (lifetime facts,
        # like prefill_traces — see reset_metrics)
        self.greedy_requests = 0
        self.sampled_requests = 0
        self.greedy_tokens = 0
        self.sampled_tokens = 0
        self.alloc.peak_pages = self.alloc.allocated_pages
        self.alloc.share_events = 0
        if self.prefix is not None:
            self.prefix.reset_hit_counters()
        if self.tier is not None:
            self.tier.reset_counters()

    def check(self) -> None:
        """Engine-level pool invariants: the allocator's shared-page-aware
        check() plus write exclusivity — the block each live request's
        next token lands in must not be shared (refcount 1), or the next
        decode step would scribble over another reader's KV."""
        self.alloc.check()
        for slot, req in enumerate(self.live):
            if req is None:
                continue
            if self.has_full:
                table = self.alloc.block_table(req.rid)
                blk = self._pos_host[slot] // self.page_size
                if blk < len(table):
                    assert self.alloc.ref(table[blk]) == 1, (
                        f"slot {slot}: next-write page {table[blk]} is "
                        f"shared (ref {self.alloc.ref(table[blk])})")
            if self.has_win:
                wrid = _win_rid(req.rid)
                live = len(self.alloc.block_table(wrid))
                bound = self.win_pages_bound(self.max_len)
                assert live <= bound, (
                    f"slot {slot}: {live} live window pages exceed the "
                    f"O(window) bound {bound} — recycling fell behind")

    def run_to_completion(self, requests: List[Request],
                          max_steps: int = 10_000) -> List[Request]:
        return _run_to_completion(self, requests, max_steps)


# ===========================================================================
# Dense-slot engine (seed baseline; serves recurrent stacks)
# ===========================================================================


class DenseServingEngine(ServingMetricsMixin):
    """Fixed-slot batch: each slot owns a dense max_len cache lane. Kept as
    the measured baseline for the paged engine and as the serving path for
    stacks with recurrent state. Retraces prefill per distinct prompt
    length and syncs the host once per live slot per step."""

    def __init__(self, cfg, params, *, slots: int = 4, max_len: int = 256,
                 rules: Rules = NO_RULES, eos_id: int = -1,
                 temperature: float = 0.0, seed: int = 0,
                 sampling: Optional[SamplingParams] = None,
                 tracer: Optional[Tracer] = None):
        self.cfg, self.params = cfg, params
        self.slots, self.max_len = slots, max_len
        self.rules, self.eos_id = rules, eos_id
        # engine-wide default policy; per-request Request.params override
        # it (same resolution as the paged engine — the two must agree
        # for the dense-vs-paged equivalence baselines to hold)
        self.default_params = (sampling if sampling is not None
                               else SamplingParams(
                                   temperature=temperature)).validate()
        self.temperature = self.default_params.temperature
        self.seed = int(seed) & 0x7FFFFFFF
        self._init_metrics(tracer)    # tracer + shared latency counters
        self.cache = api.cache_init(cfg, slots, max_len)
        self.pos = jnp.zeros((slots,), jnp.int32)
        self.cur_tok = jnp.zeros((slots, 1), jnp.int32)
        self.live: List[Optional[Request]] = [None] * slots
        self._policy: List[Optional[SamplingParams]] = [None] * slots
        self._rid_host = [0] * slots
        self._samp_idx = [0] * slots
        self._decode = jax.jit(
            lambda p, c, t, pos: api.decode_step(cfg, p, c, t, pos,
                                                 rules=rules))
        self._prefill = jax.jit(
            lambda p, b: api.prefill(cfg, p, b, rules=rules,
                                     max_len=max_len))

        def _samp(logits, pol):
            # trace-time increment: one count per compiled logit shape
            # (decode's (slots, V) + prefill's (1, V)), NOT per policy
            # value — policies are operands, so a mixed greedy+sampled
            # batch reuses the same trace (the ISSUE 9 criterion)
            # repro-lint: disable=retrace-hazard — counting traces IS the point
            self.step_traces += 1
            return sample_rows(logits[..., : cfg.vocab], pol)

        self._sample_fn = jax.jit(_samp)
        self.step_traces = 0
        self.spec_traces = 0          # dense engine has no verify step
        self.greedy_requests = 0
        self.sampled_requests = 0
        self.greedy_tokens = 0
        self.sampled_tokens = 0
        self._seen_lengths: set = set()
        self.prompt_tokens = 0
        self.prefilled_tokens = 0     # == prompt_tokens (no sharing here)

    @property
    def prefill_traces(self) -> int:
        return len(self._seen_lengths)

    # ------------------------------------------------------------------
    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.live):
            if r is None:
                return i
        return None

    def _submit(self, req: Request) -> bool:
        """Prefill `req` and install it into a free slot. False if full."""
        slot = self._free_slot()
        if slot is None:
            return False
        # the paged engine's reject-as-done guard (see PagedServingEngine.
        # submit): a prompt over the lane length would either break the
        # dynamic_update_slice cache merge below (prefill cache longer
        # than the lane) or silently clamp-overwrite the last KV row
        # (attention_decode's dense write lands at min(pos, S-1)), and a
        # request with no generation budget left can never emit — drop
        # them as done with whatever they have instead of corrupting a
        # lane or letting the scheduler retry an admission that can never
        # succeed. The threshold is deliberately the PAGED engine's
        # (>= max_len - 1, one token stricter than the dense lane strictly
        # needs): both engines must agree on which requests are servable,
        # or the dense-vs-paged equivalence baselines diverge on traces
        # that contain a boundary-length prompt.
        if (len(req.prompt) >= self.max_len - 1
                or req.max_new - len(req.generated) <= 0):
            req.done = True
            self._note_finished(req.rid)
            return True
        self._seen_lengths.add(len(req.prompt))
        toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
        tr = self.trace
        pol_req = request_params(req, self.default_params)
        pol = policy_operands([pol_req], [req.rid],
                              [len(req.generated)], self.seed)
        with tr.span("prefill_dispatch",
                     args={"len": len(req.prompt)} if tr else None):
            last_logits, cache1, pos1 = self._prefill(self.params,
                                                      {"tokens": toks})
        tok = self._sample_fn(last_logits, pol)[0]
        first = req.rid not in self.first_token_at
        with tr.span("prefill_sync"):
            req.generated.append(int(tok))
        self._policy[slot] = pol_req
        self._rid_host[slot] = req.rid
        self._samp_idx[slot] = len(req.generated)
        if first:
            if pol_req.is_greedy:
                self.greedy_requests += 1
            else:
                self.sampled_requests += 1
        self._count_tokens(pol_req, 1)
        self.prompt_tokens += len(req.prompt)
        self.prefilled_tokens += len(req.prompt)
        self._note_emitted(req.rid)
        # merge the B=1 cache lane into slot `slot` of the batched cache
        self.cache = jax.tree.map(
            lambda big, one: jax.lax.dynamic_update_slice_in_dim(
                big, one.astype(big.dtype), slot,
                axis=_batch_axis(big, one)),
            self.cache, cache1)
        self.pos = self.pos.at[slot].set(int(pos1[0]))
        self.cur_tok = self.cur_tok.at[slot, 0].set(tok)
        self.live[slot] = req
        return True

    def _step(self) -> List[Request]:
        """Advance every live slot one token. Returns [] (dense lanes are
        statically reserved, so a step never preempts). Callers use
        ``step()`` — the mixin's timed wrapper."""
        if not any(r is not None for r in self.live):
            return []
        tr = self.trace
        with tr.span("operands"):
            pol = policy_operands(self._policy, self._rid_host,
                                  self._samp_idx, self.seed)
        t0 = time.perf_counter()
        with tr.span("device_dispatch"):
            logits, self.cache = self._decode(self.params, self.cache,
                                              self.cur_tok, self.pos)
            toks = self._sample_fn(logits, pol)
            self.pos = self.pos + jnp.asarray(
                [1 if r is not None else 0 for r in self.live], jnp.int32)
            self.cur_tok = toks[:, None]
        with tr.span("host_sync"):
            # repro-lint: disable=host-sync — the dense step's one timed sync
            jax.block_until_ready(toks)  # keep the sync inside the timer
        self.step_wall_s += time.perf_counter() - t0
        self.decode_steps += 1
        with tr.span("emit"):
            for i, r in enumerate(self.live):
                if r is None:
                    continue
                t = int(toks[i])
                r.generated.append(t)
                self._samp_idx[i] += 1
                self.decoded_tokens += 1
                self._count_tokens(self._policy[i], 1)
                self._note_emitted(r.rid)
                if (t == self.eos_id or len(r.generated) >= r.max_new
                        or int(self.pos[i]) >= self.max_len - 1):
                    r.done = True
                    self.live[i] = None
                    self._policy[i] = None
                    self._note_finished(r.rid)
        return []

    def has_live(self) -> bool:
        return any(r is not None for r in self.live)

    def ensure_decode_capacity(self) -> List[Request]:
        return []                     # dense lanes never run out mid-flight

    # -- stats: the PAGED key sets, zero-filled (stable metrics() keys) ----

    def pool_stats(self) -> PoolStats:
        """Dense lanes are statically reserved — there is no pool. The
        zeros keep ``metrics()``'s key set identical to the paged
        engine's; ``dense_equiv_tokens`` reports the reservation that a
        paged pool would be measured against."""
        return PoolStats(page_size=0, num_pages=0, allocated_pages=0,
                         peak_pages=0, live_tokens=0, utilization=0.0,
                         dense_equiv_tokens=self.slots * self.max_len)

    def spec_stats(self) -> Dict[str, float]:
        return {"spec_k": 0.0, "spec_drafted": 0.0, "spec_accepted": 0.0,
                "accept_rate": 0.0, "accepted_per_step": 1.0,
                "drafter": "none"}

    def sampling_stats(self) -> Dict[str, float]:
        """Paged engine's key set, zero-filled where dense has no
        counterpart (no drafter, no verify step)."""
        return {
            "greedy_requests": float(self.greedy_requests),
            "sampled_requests": float(self.sampled_requests),
            "greedy_tokens": float(self.greedy_tokens),
            "sampled_tokens": float(self.sampled_tokens),
            "step_traces": float(self.step_traces),
            "spec_traces": float(self.spec_traces),
            "draft_proposed": 0.0,
            "draft_ingested_tokens": 0.0,
            "draft_decode_calls": 0.0,
            "draft_pool_rejects": 0.0,
        }

    def prefix_stats(self) -> Dict[str, float]:
        d = {
            "prompt_tokens": float(self.prompt_tokens),
            "prefilled_tokens": float(self.prefilled_tokens),
            "prefill_tokens_saved": 0.0,
            "prefill_saved_frac": 0.0,
            "cow_copies": 0.0,
        }
        d.update(PrefixCache.zero_stats())
        return d

    def tier_stats(self) -> Dict[str, float]:
        d: Dict[str, float] = {"host_tier": 0.0}
        d.update(HostTier.zero_stats())
        return d

    def shard_stats(self) -> Dict[str, float]:
        per = sum(leaf.size * leaf.dtype.itemsize
                  for leaf in jax.tree.leaves(self.cache))
        return {"model_shards": 1.0, "sharded_axes": "",
                "peak_pages_per_shard": 0.0,
                "pool_bytes_per_shard": float(per)}

    def _reset_subsystem_counters(self) -> None:
        self.prompt_tokens = 0
        self.prefilled_tokens = 0
        # step_traces survives (lifetime fact, like prefill_traces)
        self.greedy_requests = 0
        self.sampled_requests = 0
        self.greedy_tokens = 0
        self.sampled_tokens = 0

    def run_to_completion(self, requests: List[Request],
                          max_steps: int = 10_000) -> List[Request]:
        # the bug class PR 3 fixed in Scheduler.drain, which this
        # engine's private loop used to reintroduce by truncating
        # silently on budget exhaustion
        return _run_to_completion(self, requests, max_steps)


def _batch_axis(big, one) -> int:
    """Find the batch axis: first axis where shapes differ (slots vs 1)."""
    for ax, (b, o) in enumerate(zip(big.shape, one.shape)):
        if b != o:
            return ax
    return 0
