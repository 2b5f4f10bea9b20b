"""Serving engine benchmark: paged (in-kernel vs dense-gather decode
attention) vs the seed dense-slot engine, plus the prefix-sharing,
speculative-decode and hybrid-stack scenarios.

Seven scenarios, all generated deterministically from ``--seed`` so the CI
bench-smoke CSV artifacts are comparable run-to-run:

**mixed** — a mixed-length request trace (every prompt a different length —
the production case the dense engine handles worst) replayed through three
engines on the same model/params: the dense-slot baseline, the paged
engine with the PR-1 per-layer ``pool[block_table]`` dense gather
(``attn_impl="gather"``), and the paged engine with the Pallas flash-
decode kernel that performs the block-table gather inside the kernel
(``attn_impl="kernel"``, the default). Reported per engine:

* ``decode_tok_s``  — decoded tokens / wall time spent inside decode
  steps (engine ``step_wall_s`` telemetry), measured WARM (every engine
  is pre-compiled over the trace's lengths/buckets first): the steady-
  state decode throughput a capacity planner cares about. On CPU the
  kernel runs in Pallas interpret mode, whose per-grid-step dispatch
  cost is the same order as these toy attention shapes — wall deltas
  between gather and kernel here are noise-bound; the structural win is
  ``attn_peak_live_bytes`` (see DESIGN.md "Paged attention")
* ``trace_tok_s``   — generated tokens / whole-trace wall (prefill +
  scheduling included)
* ``ttft_mean_s``   — mean time-to-first-token across requests
* ``prefill_traces``— distinct prefill compilations: once per LENGTH
  BUCKET for paged (mixed-grained-prefetch analogue), once per distinct
  prompt length for dense
* ``kv_util`` / ``peak_kv_tokens`` — live tokens over allocated page
  capacity at peak, vs the dense engine's static ``slots * max_len``
  reservation (the paper's dynamic-allocation utilization claim)
* ``attn_peak_live_bytes`` — peak live bytes of the per-layer decode-
  attention KV working set: the gather path materializes the full
  (B, n_blocks*page, KV, D) K and V scratch every layer; the kernel path
  keeps two multi-page K and V blocks resident (the paper's separated-vs-
  shared memory access cost, measured at the serving level)

**shared-prefix** — every request opens with the same system prompt
(the "millions of users" overlap pattern); the paged[kernel] engine runs
WITHOUT and WITH the prefix cache (``runtime/prefix_cache.py``). Extra
columns: ``prefill_tokens`` (actually computed — the FLOPs proxy, since
prefill compute is linear in prefilled tokens for fixed model),
``prefill_saved_frac``, ``prefix_hit_rate`` / ``shared_token_frac``
(radix-tree telemetry), and ``peak_kv_tokens`` now reflects refcounted
page reuse. The ``prefix/noshare`` ratio row is the paper-style claim:
prefill compute and peak paging, sharing vs private.

**speculative** — templated/repetitive traffic (repeated prompt motifs —
the boilerplate pattern prompt-lookup drafting hits); the paged[kernel]
engine runs with ``spec_k=0`` (the T=1 baseline) and with ``--spec-k``
drafted tokens verified per multi-token step. Extra columns:
``decode_steps`` (each one streams the full weights + live pages once —
the memory-bound cost speculative decode amortizes),
``accepted_per_step`` (tokens emitted per request per verify step; the
baseline is 1.0 by construction) and ``accept_rate``. The ``specK/T=1``
ratio row is the claim: identical greedy tokens in fewer weight/KV
streams, i.e. decode arithmetic intensity multiplied by
``accepted_per_step`` at unchanged page traffic.

**sampling** — decode policies (ISSUE 9): the templated trace replayed
greedy (plain / n-gram spec / draft-model spec — all three must emit
IDENTICAL tokens; ``tokens_match_greedy`` is CI-gated by
``benchmarks/check_csv.py``) and sampled (temperature + top-p, per-request
``SamplingParams``, plain and rejection-sampled speculative — their
exactness claim is distributional, tested in tests/test_sampling.py, so
the match cell stays empty). Extra columns: ``accept_rate`` /
``drafter_kind`` for spec rows, ``sampled_tokens`` and the
``step_traces`` / ``spec_traces`` retrace telemetry (policies are traced-
program OPERANDS — greedy and sampled requests share one compilation).
The draft-model rows self-draft (target model == draft model, both
smoke-sized), exercising the drafter's incremental paged-KV sync without
a second arch's weights.

**hybrid** — a griffin-style hybrid stack (``recurrentgemma-9b`` smoke:
rglru + local_attn sliding window) with prompts LONGER than the window,
replayed through the dense baseline and the paged engine under both attn
impls. This is ISSUE 5's claim: windowed layers get paged ring buffers
whose pages are *recycled* as they slide out of the window
(``PageAllocator.release_prefix``), so ``peak_kv_tokens`` stays O(window)
per request while the dense engine reserves ``slots * max_len``; recurrent
layers ride along in fixed-size state slots. Extra columns:
``win_recycled_pages`` (pages slid out and freed), ``win_page_bound``
(ceil(window/page) + 1 — the per-request live-page ceiling the engine
enforces), and the ``paged/dense`` ratio row's ``peak_kv_tokens`` is the
headline (window / max_len-bound memory, identical greedy tokens).

**sharded** — the mixed trace through the paged[kernel] engine at
model = 1/2/4 tensor-parallel shards (``parallel/tp.py`` over
``launch/mesh.make_host_mesh`` meshes; on CPU CI the devices come from
``XLA_FLAGS=--xla_force_host_platform_device_count=4``). Extra columns:
``model_shards`` / ``sharded_axes``, ``peak_pages_per_shard`` (equals the
allocator peak — block tables are replicated, each shard holds its
KV-head slice of the same page set), ``pool_bytes_per_shard`` (what TP
actually divides) and ``tokens_match_tp1`` (every shard count must emit
the single-shard engine's exact greedy tokens). Shard counts the backend
cannot fold are emitted as skip-note rows, not dropped.

**oversubscribe** — working set >> device pool (ISSUE 7): the mixed trace
through an unconstrained paged engine, then through pools capped at ~40%
of the trace's KV footprint, evict-only vs host-tiered
(``runtime/host_tier.py``). Every capped row must report
``tokens_match_unconstrained=1`` — a capped pool may change WHEN tokens
are computed, never WHICH — and CI's ``benchmarks/check_csv.py`` gate
fails the build on any other value. The tiered rows' headline is
``reprefill_tokens_saved`` (prefill compute the evict-only engine
re-spent on preemption-resume that swap-in did not) plus the streamer
telemetry: ``prefetch_hit_rate`` / ``copy_stall_ticks`` /
``host_bytes_peak``. Prefix-cache and hybrid pairs ride along so all
three demotion sources (idle radix nodes, preempted requests incl.
recurrent state, slid-out window pages) run inside the timed replay.

Every scenario row additionally carries the unified latency/utilization
columns from ``engine.metrics()`` (ISSUE 8): ``ttft_p50`` / ``ttft_p95``
(arrival-to-first-token, queue wait included), ``tpot_p50`` / ``tpot_p95``
(per-token decode latency) and ``temporal_util`` (device-step wall over
decode-tick wall — the serving analogue of the paper's Fig. 6 temporal-
utilization breakdown). ``--trace-out trace.json`` exports the whole run
as Chrome Trace Event JSON, loadable in Perfetto.

  PYTHONPATH=src python -m benchmarks.serve_bench [--arch qwen2.5-3b]
      [--seed 0] [--trace-out trace.json]
      [--scenario mixed|shared-prefix|speculative|sampling|hybrid|sharded|
       oversubscribe|all]

(the hybrid scenario pins its own arch — recurrentgemma-9b smoke — since
it exists to exercise the windowed/recurrent block kinds.)
"""
from __future__ import annotations

import argparse
import random
import sys
import time
from typing import Dict, List, Optional

import jax

from benchmarks.common import emit
from repro.configs import get_smoke_config
from repro.models import api
from repro.runtime.scheduler import Scheduler
from repro.runtime.serving import (DenseServingEngine, PagedServingEngine,
                                   Request)
from repro.runtime.trace import NULL_TRACER, Tracer, set_default_tracer


def _trace(cfg, n_requests: int, max_new: int, seed: int) -> List[Request]:
    """Mixed-length trace: all prompt lengths distinct (3, 8, 13, ...),
    spanning several power-of-two buckets; token ids drawn from the seeded
    rng so the trace is identical for identical seeds."""
    rng = random.Random(seed)
    return [Request(rid=i,
                    prompt=[rng.randrange(cfg.vocab)
                            for _ in range(3 + 5 * i)],
                    max_new=max_new)
            for i in range(n_requests)]


def _shared_trace(cfg, n_requests: int, max_new: int, seed: int,
                  sys_len: int) -> List[Request]:
    """Shared-system-prompt trace: every request = the same ``sys_len``
    token system prompt + a short per-request tail (deterministic in
    ``seed``)."""
    rng = random.Random(seed)
    sys_prompt = [rng.randrange(cfg.vocab) for _ in range(sys_len)]
    return [Request(rid=i,
                    prompt=sys_prompt + [rng.randrange(cfg.vocab)
                                         for _ in range(2 + i % 5)],
                    max_new=max_new)
            for i in range(n_requests)]


def _spec_trace(cfg, n_requests: int, max_new: int, seed: int,
                motif_len: int = 6, reps: int = 4) -> List[Request]:
    """Templated/repetitive trace: every prompt is a repeated motif (the
    boilerplate / few-shot / structured-output pattern prompt-lookup
    drafting feeds on) behind a short per-request salt, so requests differ
    but their contexts — and the repetitive spans the model then emits —
    give the n-gram drafter something to hit."""
    rng = random.Random(seed)
    motif = [rng.randrange(cfg.vocab) for _ in range(motif_len)]
    return [Request(rid=i,
                    prompt=[rng.randrange(cfg.vocab)
                            for _ in range(i % 3)] + motif * reps,
                    max_new=max_new)
            for i in range(n_requests)]


def _warm(engine, mk_trace) -> None:
    """Compile-warm the engine: replay the trace's prompt lengths (covers
    every prefill trace/bucket for dense AND paged) with max_new=2 for a
    couple of decode steps, so the timed replay measures steady-state
    serving rather than jit tracing — the number a capacity planner
    wants is the warm one."""
    sched = Scheduler(engine)
    for r in mk_trace(2):
        sched.add(r)
    sched.drain(max_steps=1000)
    # warmup compiled + ran; zero the telemetry the timed replay reports.
    # One call owns the whole reset contract (engine counters, latency
    # stamps, pool high-water marks, prefix hit counters, tier transfer
    # rates) so benches can't drift out of sync with new subsystems —
    # warmed STATE (radix tree contents, demoted host nodes, jit caches)
    # survives; only the counters the replay reports are zeroed.
    engine.reset_metrics()


def _attn_peak_live_bytes(cfg, engine) -> int:
    """Peak live bytes of the per-layer decode-attention KV working set."""
    kv, hd = cfg.kv_heads, cfg.resolved_head_dim
    if isinstance(engine, PagedServingEngine) \
            and engine.attn_impl == "kernel":
        # two K + two V blocks resident (double buffered), in the pool's
        # storage dtype (int8 pools dequantize inside the kernel)
        itemsize = 1 if cfg.kv_cache_dtype == "int8" else 2
        tokens = engine._kv_block_tokens or engine.page_size
        return 4 * tokens * kv * hd * itemsize
    # dense lanes / dense gather: the whole (B, max_len, KV, D) K and V,
    # materialized DEQUANTIZED to the 2-byte activation dtype
    # (layers.kv_dequant) regardless of the cache storage dtype. A dense
    # engine whose attention is ALL sliding-window (griffin-style: no
    # full-attention kinds) only ever holds window-sized rings, so its
    # working set is window-bounded; any full-attention layer in the
    # pattern holds max_len lanes (the paged gather baseline always
    # materializes the full table length).
    seq = engine.max_len
    if not isinstance(engine, PagedServingEngine) and cfg.hybrid is not None:
        from repro.models.api import PAGEABLE_KINDS
        if not set(cfg.hybrid.pattern) & set(PAGEABLE_KINDS):
            seq = min(seq, cfg.hybrid.window)
    return 2 * engine.slots * seq * kv * hd * 2


def _drive(engine, reqs: List[Request], max_steps: int, cfg,
           name: Optional[str] = None) -> Dict:
    sched = Scheduler(engine)
    for r in reqs:
        sched.add(r)
    t0 = time.perf_counter()
    sched.drain(max_steps=max_steps, on_exhaust="warn")
    wall = time.perf_counter() - t0
    done = [r for r in reqs if r.done]
    toks = sum(len(r.generated) for r in done)
    if name is None:
        name = type(engine).__name__
        if isinstance(engine, PagedServingEngine):
            name += f"[{engine.attn_impl}]"
    # latency percentiles + temporal utilization come from the unified
    # metrics surface (arrival stamped at Scheduler.add, so TTFT includes
    # queue wait — the number a latency SLO is written against)
    m = engine.metrics()
    row = {
        "engine": name,
        "requests_done": len(done),
        "tokens": toks,
        "wall_s": wall,
        "decode_tok_s": engine.decoded_tokens / engine.step_wall_s
        if engine.step_wall_s else 0.0,
        "trace_tok_s": toks / wall if wall else 0.0,
        "ttft_mean_s": m["latency.ttft_mean_s"],
        "ttft_p50": m["latency.ttft_p50_s"],
        "ttft_p95": m["latency.ttft_p95_s"],
        "tpot_p50": m["latency.tpot_p50_s"],
        "tpot_p95": m["latency.tpot_p95_s"],
        "temporal_util": m["util.temporal"],
        "prefill_traces": engine.prefill_traces,
        "sched_exhausted": int(sched.exhausted),
    }
    if isinstance(engine, PagedServingEngine):
        st = engine.pool_stats()
        row["peak_kv_tokens"] = st.peak_pages * st.page_size
        row["kv_util_vs_dense"] = (st.peak_pages * st.page_size
                                   / st.dense_equiv_tokens)
    else:
        row["peak_kv_tokens"] = engine.slots * engine.max_len
        row["kv_util_vs_dense"] = 1.0
    row["attn_peak_live_bytes"] = _attn_peak_live_bytes(cfg, engine)
    return row


def _run_mixed(cfg, params, slots, max_len, n_requests, max_new,
               seed) -> List[Dict]:
    def mk(new):
        return _trace(cfg, n_requests, new, seed)

    rows = []
    dense = DenseServingEngine(cfg, params, slots=slots, max_len=max_len)
    _warm(dense, mk)
    rows.append(_drive(dense, mk(max_new), 4000, cfg))
    for impl in ("gather", "kernel"):
        paged = PagedServingEngine(cfg, params, slots=slots,
                                   max_len=max_len, attn_impl=impl)
        _warm(paged, mk)
        rows.append(_drive(paged, mk(max_new), 4000, cfg))
    d, g, k = rows[0], rows[1], rows[2]

    def ratio_row(name: str, base: Dict) -> Dict:
        """Summary row: kernel engine vs `base` (counts as deltas,
        times/bytes as base/kernel speedup or kernel/base footprint)."""
        return {
            "engine": name,
            "requests_done": k["requests_done"] - base["requests_done"],
            "tokens": k["tokens"] - base["tokens"],
            "wall_s": base["wall_s"] / k["wall_s"],
            "decode_tok_s": k["decode_tok_s"] / base["decode_tok_s"],
            "trace_tok_s": k["trace_tok_s"] / base["trace_tok_s"],
            "ttft_mean_s": base["ttft_mean_s"] / k["ttft_mean_s"]
            if k["ttft_mean_s"] else 0.0,
            "prefill_traces": k["prefill_traces"] - base["prefill_traces"],
            "peak_kv_tokens": k["peak_kv_tokens"] - base["peak_kv_tokens"],
            "kv_util_vs_dense": k["kv_util_vs_dense"],
            "attn_peak_live_bytes": k["attn_peak_live_bytes"]
            / base["attn_peak_live_bytes"],
        }

    rows.append(ratio_row("kernel/gather", g))
    rows.append(ratio_row("kernel/dense", d))
    return rows


def _run_shared_prefix(cfg, params, slots, max_len, n_requests, max_new,
                       seed, sys_len) -> List[Dict]:
    def mk(new):
        return _shared_trace(cfg, n_requests, new, seed, sys_len)

    rows = []
    for share, name in ((False, "paged[kernel,noshare]"),
                        (True, "paged[kernel,prefix]")):
        eng = PagedServingEngine(cfg, params, slots=slots, max_len=max_len,
                                 attn_impl="kernel", prefix_cache=share)
        _warm(eng, mk)
        row = _drive(eng, mk(max_new), 4000, cfg, name=name)
        ps = eng.prefix_stats()
        row["prefill_tokens"] = int(ps["prefilled_tokens"])
        row["prefill_saved_frac"] = ps["prefill_saved_frac"]
        row["prefix_hit_rate"] = ps.get("hit_rate", 0.0)
        row["shared_token_frac"] = ps.get("shared_token_frac", 0.0)
        row["cow_copies"] = int(ps["cow_copies"])
        rows.append(row)
    base, pref = rows
    rows.append({
        "engine": "prefix/noshare",
        "requests_done": pref["requests_done"] - base["requests_done"],
        "tokens": pref["tokens"] - base["tokens"],
        "wall_s": base["wall_s"] / pref["wall_s"] if pref["wall_s"] else 0.0,
        "trace_tok_s": pref["trace_tok_s"] / base["trace_tok_s"]
        if base["trace_tok_s"] else 0.0,
        "ttft_mean_s": base["ttft_mean_s"] / pref["ttft_mean_s"]
        if pref["ttft_mean_s"] else 0.0,
        # the two headline savings: prefill compute (token-linear FLOPs
        # proxy) and peak physical paging, sharing vs no sharing
        "prefill_tokens": pref["prefill_tokens"] - base["prefill_tokens"],
        "prefill_saved_frac": 1.0 - (pref["prefill_tokens"]
                                     / base["prefill_tokens"])
        if base["prefill_tokens"] else 0.0,
        "peak_kv_tokens": pref["peak_kv_tokens"] - base["peak_kv_tokens"],
        "kv_util_vs_dense": pref["kv_util_vs_dense"],
        "prefix_hit_rate": pref["prefix_hit_rate"],
        "shared_token_frac": pref["shared_token_frac"],
    })
    return rows


def _run_speculative(cfg, params, slots, max_len, n_requests, max_new,
                     seed, spec_k) -> List[Dict]:
    def mk(new):
        return _spec_trace(cfg, n_requests, new, seed)

    rows = []
    for k, name in ((0, "paged[kernel,T=1]"),
                    (spec_k, f"paged[kernel,spec{spec_k}]")):
        eng = PagedServingEngine(cfg, params, slots=slots, max_len=max_len,
                                 attn_impl="kernel", spec_k=k)
        _warm(eng, mk)
        row = _drive(eng, mk(max_new), 4000, cfg, name=name)
        ss = eng.spec_stats()
        row["decode_steps"] = eng.decode_steps
        row["accepted_per_step"] = ss["accepted_per_step"]
        row["accept_rate"] = ss["accept_rate"]
        row["spec_drafted"] = int(ss["spec_drafted"])
        row["spec_accepted"] = int(ss["spec_accepted"])
        rows.append(row)
    base, spec = rows
    rows.append({
        "engine": f"spec{spec_k}/T=1",
        "requests_done": spec["requests_done"] - base["requests_done"],
        "tokens": spec["tokens"] - base["tokens"],
        "wall_s": base["wall_s"] / spec["wall_s"] if spec["wall_s"] else 0.0,
        "decode_tok_s": spec["decode_tok_s"] / base["decode_tok_s"]
        if base["decode_tok_s"] else 0.0,
        "trace_tok_s": spec["trace_tok_s"] / base["trace_tok_s"]
        if base["trace_tok_s"] else 0.0,
        # the headline pair: the SAME tokens in fewer verify steps (each
        # step = one full weight + live-page stream), i.e. arithmetic
        # intensity up by accepted_per_step at unchanged page traffic
        "decode_steps": spec["decode_steps"] - base["decode_steps"],
        "accepted_per_step": spec["accepted_per_step"],
        "accept_rate": spec["accept_rate"],
    })
    return rows


def _run_sampling(cfg, params, slots, max_len, n_requests, max_new,
                  seed, spec_k) -> List[Dict]:
    """Decode-policy rows (ISSUE 9) over the templated trace: three
    greedy engines (plain, n-gram spec, draft-model spec) that must all
    emit IDENTICAL tokens (``tokens_match_greedy`` — the CI-gated
    exactness claim), then the same three under a per-request sampled
    policy (temperature 0.9, top-p 0.95) where the speculative rows'
    claim is the acceptance rate at unchanged output DISTRIBUTION (the
    chi-square suite in tests/test_sampling.py; the match cell stays
    empty — token equality is not the sampled contract)."""
    from repro.runtime.drafter import DraftModelDrafter
    from repro.runtime.sampling import SamplingParams

    sampled = SamplingParams(temperature=0.9, top_p=0.95, seed=seed)

    def mk(new, pol=None):
        reqs = _spec_trace(cfg, n_requests, new, seed)
        for r in reqs:
            r.params = pol
        return reqs

    def draft():
        # self-draft: the target model doubles as the draft model (both
        # smoke-sized) — deterministic, so greedy rows stay exact, and
        # the drafter's incremental paged-KV sync runs for real
        return DraftModelDrafter(cfg, params, max_len=max_len)

    rows: List[Dict] = []
    greedy_toks = None
    for name, kw, pol in (
            ("paged[kernel,greedy]", {}, None),
            (f"paged[kernel,spec{spec_k},greedy]", {"spec_k": spec_k},
             None),
            (f"paged[kernel,draft{spec_k},greedy]",
             {"spec_k": spec_k, "drafter": draft()}, None),
            ("paged[kernel,sampled]", {}, sampled),
            (f"paged[kernel,spec{spec_k},sampled]", {"spec_k": spec_k},
             sampled),
            (f"paged[kernel,draft{spec_k},sampled]",
             {"spec_k": spec_k, "drafter": draft()}, sampled)):
        eng = PagedServingEngine(cfg, params, slots=slots, max_len=max_len,
                                 attn_impl="kernel", **kw)
        _warm(eng, lambda n, _p=pol: mk(n, _p))
        reqs = mk(max_new, pol)
        row = _drive(eng, reqs, 4000, cfg, name=name)
        m = eng.metrics()
        row["sampled_tokens"] = int(m["sampling.sampled_tokens"])
        # retrace telemetry: ONE step trace (and one spec trace when
        # speculative) no matter the greedy/sampled request mix —
        # policies ride in as operands, never as trace constants
        row["step_traces"] = int(m["sampling.step_traces"])
        row["spec_traces"] = int(m["sampling.spec_traces"])
        if kw.get("spec_k"):
            ss = eng.spec_stats()
            row["accept_rate"] = ss["accept_rate"]
            row["accepted_per_step"] = ss["accepted_per_step"]
            row["drafter_kind"] = ss["drafter"]
        if pol is None:
            toks = [list(r.generated) for r in reqs]
            if greedy_toks is None:
                greedy_toks = toks
            row["tokens_match_greedy"] = int(toks == greedy_toks)
        rows.append(row)
    return rows


def _hybrid_trace(cfg, n_requests: int, max_new: int, seed: int,
                  window: int) -> List[Request]:
    """Prompts straddling the attention window (some shorter, most
    longer), so admission, recycling and the window-boundary masking all
    run inside the timed replay."""
    rng = random.Random(seed)
    return [Request(rid=i,
                    prompt=[rng.randrange(cfg.vocab)
                            for _ in range(window // 2 + (5 * i) % (2 * window))],
                    max_new=max_new)
            for i in range(n_requests)]


def _run_hybrid(slots, max_len, n_requests, max_new, seed) -> List[Dict]:
    cfg = get_smoke_config("recurrentgemma-9b")
    params = api.init_params(cfg, jax.random.key(0))
    window = cfg.hybrid.window

    def mk(new):
        return _hybrid_trace(cfg, n_requests, new, seed, window)

    rows = []
    dense = DenseServingEngine(cfg, params, slots=slots, max_len=max_len)
    _warm(dense, mk)
    rows.append(_drive(dense, mk(max_new), 4000, cfg,
                       name="dense[hybrid]"))
    for impl in ("gather", "kernel"):
        paged = PagedServingEngine(cfg, params, slots=slots,
                                   max_len=max_len, attn_impl=impl)
        _warm(paged, mk)
        row = _drive(paged, mk(max_new), 4000, cfg,
                     name=f"paged[{impl},hybrid]")
        row["win_recycled_pages"] = paged.win_recycled_pages
        row["win_page_bound"] = paged.win_pages_bound(max_len)
        rows.append(row)
    d, k = rows[0], rows[2]
    rows.append({
        "engine": "paged/dense[hybrid]",
        "requests_done": k["requests_done"] - d["requests_done"],
        "tokens": k["tokens"] - d["tokens"],
        "wall_s": d["wall_s"] / k["wall_s"] if k["wall_s"] else 0.0,
        "decode_tok_s": k["decode_tok_s"] / d["decode_tok_s"]
        if d["decode_tok_s"] else 0.0,
        "trace_tok_s": k["trace_tok_s"] / d["trace_tok_s"]
        if d["trace_tok_s"] else 0.0,
        # the headline: peak physical KV, O(window)-recycled pages vs the
        # dense engine's slots * max_len reservation — same greedy tokens
        "peak_kv_tokens": k["peak_kv_tokens"] - d["peak_kv_tokens"],
        "kv_util_vs_dense": k["kv_util_vs_dense"],
        "win_recycled_pages": k["win_recycled_pages"],
        "win_page_bound": k["win_page_bound"],
    })
    return rows


def _run_sharded(cfg, params, slots, max_len, n_requests, max_new,
                 seed) -> List[Dict]:
    """Tensor-parallel scaling rows (ISSUE 6): the same mixed trace
    through the paged[kernel] engine at model=1/2/4 shards, one
    ``("data","model")`` mesh per shard count over the first ``s`` visible
    devices (single-shard = no mesh — the baseline every sharded row must
    match token-for-token). Shard counts the backend can't fold (fewer
    devices than shards — e.g. a CI leg without the forced-host-device
    flag) are skipped with a note row, NOT silently dropped: an empty
    scaling table must say why. Per-shard columns come from
    ``engine.shard_stats()``: pages are allocated logically and block
    tables are replicated, so ``peak_pages_per_shard`` equals the
    allocator's peak while ``pool_bytes_per_shard`` is what tensor
    parallelism actually divides."""
    from repro.launch.mesh import make_host_mesh

    def mk(new):
        return _trace(cfg, n_requests, new, seed)

    n_dev = len(jax.devices())
    rows: List[Dict] = []
    baseline: Optional[List[List[int]]] = None
    for s in (1, 2, 4):
        if s > n_dev:
            rows.append({"engine": f"paged[kernel,tp{s}]",
                         "model_shards": s, "skipped":
                         f"needs {s} devices, have {n_dev} "
                         f"(XLA_FLAGS=--xla_force_host_platform_"
                         f"device_count=4)"})
            continue
        mesh = make_host_mesh(model=s, devices=jax.devices()[:s]) \
            if s > 1 else None
        eng = PagedServingEngine(cfg, params, slots=slots, max_len=max_len,
                                 attn_impl="kernel", mesh=mesh)
        _warm(eng, mk)
        reqs = mk(max_new)
        row = _drive(eng, reqs, 4000, cfg, name=f"paged[kernel,tp{s}]")
        toks = [r.generated for r in reqs]
        if baseline is None:
            baseline = toks
        st = eng.shard_stats()
        row["model_shards"] = int(st["model_shards"])
        row["sharded_axes"] = st["sharded_axes"] or "-"
        row["peak_pages_per_shard"] = int(st["peak_pages_per_shard"])
        row["pool_bytes_per_shard"] = int(st["pool_bytes_per_shard"])
        # the contract the scaling table rides on: every shard count
        # emits the SAME greedy tokens — a row that didn't is not a
        # data point, it's a bug, and the CSV must say so
        row["tokens_match_tp1"] = int(toks == baseline)
        rows.append(row)
    return rows


def _pool_cap(reqs: List[Request], max_len: int, page_size: int,
              frac: float = 0.4) -> int:
    """Device-pool cap (in pages) for the oversubscribe scenario: ``frac``
    of the trace's total worst-case KV footprint, floored at one page above
    the largest single request (below that the engine rightly rejects the
    request as infeasible rather than thrashing)."""
    need = [-(-min(len(r.prompt) + r.max_new, max_len) // page_size)
            for r in reqs]
    return max(int(sum(need) * frac), max(need) + 1)


def _run_oversubscribe(cfg, params, slots, max_len, n_requests, max_new,
                       seed, sys_len) -> List[Dict]:
    """Working set >> device pool (ISSUE 7): the mixed trace replayed
    through an unconstrained paged engine, then through engines whose pool
    is capped at ~40% of the trace's KV footprint — once with eviction-only
    preemption (resume = destructive re-prefill) and once with the host
    tier on (resume = swap-in from host RAM). Every capped row must emit
    the unconstrained engine's exact greedy tokens
    (``tokens_match_unconstrained`` — CI's check_csv gate fails the build
    otherwise); the tiered row's claim is ``reprefill_tokens_saved``:
    prefill compute the evict-only engine re-spent that the tier's
    promote path did not. A prefix-cache pair (shared-prefix trace, radix
    nodes demote to host instead of LRU-evicting and promote on hit) and a
    hybrid pair (recurrentgemma: recurrent STATE swaps with the pages)
    ride along so every demotion source is exercised."""
    rows: List[Dict] = []

    def mk(new):
        return _trace(cfg, n_requests, new, seed)

    for impl in ("gather", "kernel"):
        base = PagedServingEngine(cfg, params, slots=slots, max_len=max_len,
                                  attn_impl=impl)
        cap = _pool_cap(mk(max_new), max_len, base.page_size)
        _warm(base, mk)
        reqs = mk(max_new)
        row = _drive(base, reqs, 8000, cfg, name=f"paged[{impl},uncapped]")
        row["pool_pages"] = base.alloc.num_pages
        base_toks = [list(r.generated) for r in reqs]
        base_prefilled = base.prefilled_tokens
        rows.append(row)
        evict_reprefill = 0
        for tier, name in ((False, f"paged[{impl},evict@cap]"),
                           (True, f"paged[{impl},tiered@cap]")):
            eng = PagedServingEngine(cfg, params, slots=slots,
                                     max_len=max_len, attn_impl=impl,
                                     num_pages=cap, host_tier=tier)
            _warm(eng, mk)
            reqs = mk(max_new)
            row = _drive(eng, reqs, 8000, cfg, name=name)
            row["pool_pages"] = cap
            row["preemptions"] = sum(r.preemptions for r in reqs)
            # the contract the whole scenario rides on: a capped pool may
            # change WHEN tokens are computed, never WHICH tokens
            row["tokens_match_unconstrained"] = \
                int([list(r.generated) for r in reqs] == base_toks)
            # prefill compute re-spent on preemption-resume (0 for the
            # unconstrained engine by construction)
            row["reprefill_tokens"] = eng.prefilled_tokens - base_prefilled
            if not tier:
                evict_reprefill = row["reprefill_tokens"]
            else:
                ts = eng.tier_stats()
                row["reprefill_tokens_saved"] = \
                    evict_reprefill - row["reprefill_tokens"]
                for k in ("swap_outs", "swap_ins", "demoted_pages",
                          "promoted_pages", "prefetch_hit_rate",
                          "copy_stall_ticks", "host_bytes_peak"):
                    row[k] = ts[k]
            rows.append(row)

    # prefix-cache pair: idle radix nodes demote to host before LRU
    # eviction; radix hits on host-resident nodes promote (prefetched a
    # tick early) instead of re-prefilling the shared system prompt
    def mk_shared(new):
        return _shared_trace(cfg, n_requests, new, seed, sys_len)

    base = PagedServingEngine(cfg, params, slots=slots, max_len=max_len,
                              attn_impl="kernel", prefix_cache=True)
    cap = _pool_cap(mk_shared(max_new), max_len, base.page_size)
    _warm(base, mk_shared)
    reqs = mk_shared(max_new)
    row = _drive(base, reqs, 8000, cfg, name="paged[kernel,prefix,uncapped]")
    row["pool_pages"] = base.alloc.num_pages
    base_toks = [list(r.generated) for r in reqs]
    rows.append(row)
    eng = PagedServingEngine(cfg, params, slots=slots, max_len=max_len,
                             attn_impl="kernel", prefix_cache=True,
                             num_pages=cap, host_tier=True)
    _warm(eng, mk_shared)
    reqs = mk_shared(max_new)
    row = _drive(eng, reqs, 8000, cfg, name="paged[kernel,prefix,tiered@cap]")
    row["pool_pages"] = cap
    row["preemptions"] = sum(r.preemptions for r in reqs)
    row["tokens_match_unconstrained"] = \
        int([list(r.generated) for r in reqs] == base_toks)
    ts = eng.tier_stats()
    for k in ("cache_demotions", "cache_promotions", "prefetch_hit_rate",
              "copy_stall_ticks", "host_bytes_peak"):
        row[k] = ts[k]
    rows.append(row)

    # hybrid pair: a preempted recurrentgemma request swaps its recurrent
    # state slots AND its window pages to host — resume restores both
    # (no re-prefill; PR 5 resumed these by re-prefilling)
    hcfg = get_smoke_config("recurrentgemma-9b")
    hparams = api.init_params(hcfg, jax.random.key(0))
    hn, hnew = max(4, n_requests // 2), max(max_new, 24)

    def mk_hybrid(new):
        return _hybrid_trace(hcfg, hn, new, seed, hcfg.hybrid.window)

    base = PagedServingEngine(hcfg, hparams, slots=slots, max_len=max_len,
                              attn_impl="gather")
    cap = _pool_cap(mk_hybrid(hnew), max_len, base.page_size)
    _warm(base, mk_hybrid)
    reqs = mk_hybrid(hnew)
    row = _drive(base, reqs, 8000, hcfg, name="paged[hybrid,uncapped]")
    row["pool_pages"] = base.alloc.num_pages
    base_toks = [list(r.generated) for r in reqs]
    rows.append(row)
    eng = PagedServingEngine(hcfg, hparams, slots=slots, max_len=max_len,
                             attn_impl="gather", num_pages=cap,
                             host_tier=True)
    _warm(eng, mk_hybrid)
    reqs = mk_hybrid(hnew)
    row = _drive(eng, reqs, 8000, hcfg, name="paged[hybrid,tiered@cap]")
    row["pool_pages"] = cap
    row["preemptions"] = sum(r.preemptions for r in reqs)
    row["tokens_match_unconstrained"] = \
        int([list(r.generated) for r in reqs] == base_toks)
    ts = eng.tier_stats()
    for k in ("swap_outs", "swap_ins", "win_archived_pages",
              "prefetch_hit_rate", "copy_stall_ticks", "host_bytes_peak"):
        row[k] = ts[k]
    rows.append(row)
    return rows


def run(arch: str = "qwen2.5-3b", slots: int = 4, max_len: int = 128,
        n_requests: int = 12, max_new: int = 8, smoke: bool = False,
        seed: int = 0, scenario: str = "all",
        sys_len: int = 48, spec_k: int = 4,
        trace_out: Optional[str] = None) -> List[Dict]:
    if smoke:       # decode-heavy but small: seconds, not minutes, with
        # enough steps that decode_tok_s isn't measuring scheduler noise
        slots, max_len, n_requests, max_new = 2, 128, 4, 24
        sys_len = 24
    cfg = get_smoke_config(arch)
    params = api.init_params(cfg, jax.random.key(0))
    # --trace-out: install a process-default tracer so EVERY engine the
    # scenarios construct (they build their own) records into one timeline;
    # exported as Chrome Trace Event JSON (open in Perfetto / about:tracing)
    tracer = Tracer(enabled=True) if trace_out else None
    if tracer is not None:
        set_default_tracer(tracer)
    try:
        rows: List[Dict] = []
        if scenario in ("mixed", "all"):
            rows += _run_mixed(cfg, params, slots, max_len, n_requests,
                               max_new, seed)
        if scenario in ("shared-prefix", "all"):
            rows += _run_shared_prefix(cfg, params, slots, max_len,
                                       n_requests, max_new, seed, sys_len)
        if scenario in ("speculative", "all"):
            # speculative decode is a decode-tail story (every verify step
            # amortizes one full weight+page stream): give it a decode-heavy
            # trace even when the other scenarios run short ones
            rows += _run_speculative(cfg, params, slots, max_len,
                                     n_requests, max(max_new, 24), seed,
                                     spec_k)
        if scenario in ("sampling", "all"):
            # decode policies ride the templated trace too: the greedy
            # spec rows must match the greedy baseline token-for-token,
            # and a decode-heavy tail gives the sampled rows real
            # acceptance statistics
            rows += _run_sampling(cfg, params, slots, max_len, n_requests,
                                  max(max_new, 24), seed, spec_k)
        if scenario in ("hybrid", "all"):
            # windowed/recurrent stacks pin their own arch (recurrentgemma
            # smoke) and a decode tail long enough to slide past the window
            rows += _run_hybrid(slots, max_len, max(4, n_requests // 2),
                                max(max_new, 24), seed)
        if scenario in ("sharded", "all"):
            rows += _run_sharded(cfg, params, slots, max_len, n_requests,
                                 max_new, seed)
        if scenario in ("oversubscribe", "all"):
            # host-tier oversubscription is a preemption story: decode tails
            # long enough that capped pools MUST preempt mid-generation
            rows += _run_oversubscribe(cfg, params, slots, max_len,
                                       n_requests, max(max_new, 24), seed,
                                       sys_len)
    finally:
        if tracer is not None:
            set_default_tracer(NULL_TRACER)
            tracer.export(trace_out)
            print(f"# wrote {trace_out}: {len(tracer.events())} events "
                  f"({tracer.dropped_events} dropped)", file=sys.stderr)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0,
                    help="trace-generation seed (same seed -> same trace, "
                         "so CI CSV artifacts are comparable run-to-run)")
    ap.add_argument("--scenario",
                    choices=["mixed", "shared-prefix", "speculative",
                             "sampling", "hybrid", "sharded",
                             "oversubscribe", "all"],
                    default="all")
    ap.add_argument("--sys-len", type=int, default=48,
                    help="shared system-prompt length for shared-prefix")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per verify step for speculative")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace (seconds): CI per-PR regression signal")
    ap.add_argument("--trace-out", default=None, metavar="TRACE.JSON",
                    help="export a Chrome Trace Event JSON of the whole "
                         "run (open in Perfetto / about:tracing; validate "
                         "with python -m repro.runtime.trace)")
    args = ap.parse_args()
    rows = run(args.arch, args.slots, args.max_len, args.requests,
               args.max_new, smoke=args.smoke, seed=args.seed,
               scenario=args.scenario, sys_len=args.sys_len,
               spec_k=args.spec_k, trace_out=args.trace_out)
    print(emit(rows))


if __name__ == "__main__":
    main()
