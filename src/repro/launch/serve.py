"""Serving launcher: batched request serving for any assigned arch.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --smoke \
      [--slots 4] [--requests 8] [--max-new 12] [--engine paged|dense] \
      [--page-size 16] [--num-pages N] [--paged-attn kernel|gather] \
      [--prefix-cache] [--spec-k K] [--draft-model ARCH] [--shards M] \
      [--replicas R] [--host-tier] [--temperature T] [--top-k K] \
      [--top-p P] [--trace [trace.json]]

Every decoder-only stack defaults to the paged KV-cache engine (continuous
batching over a shared page pool, bucketed prefill) — hybrid stacks
included: sliding-window layers get paged ring buffers whose pages are
recycled as they slide out of the window (O(window) live pages per
request), recurrent layers get fixed-size state slots. Only
encoder-decoder stacks fall back to the dense-slot engine (with a warning
naming any paged-engine kwargs that fallback drops).

``--shards M`` serves tensor-parallel over M devices (KV pools + attn/mlp
weights sharded on a ("data","model") mesh; same greedy tokens as M=1);
``--replicas R`` runs R data-parallel engine replicas behind a router
(R x M devices total — on CPU, force devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models import api
from repro.runtime.drafter import DraftModelDrafter
from repro.runtime.router import make_replicas
from repro.runtime.sampling import SamplingParams
from repro.runtime.serving import (DenseServingEngine, PagedServingEngine,
                                   Request, ServingEngine)
from repro.runtime.trace import Tracer, set_default_tracer


def add_server_flags(ap: argparse.ArgumentParser) -> None:
    """The flags that choose, size and configure the engine: what
    ``build_server`` reads."""
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="decode policy: 0 = greedy (default), > 0 samples "
                         "(runtime/sampling.py — works with --spec-k via "
                         "rejection-sampled verification)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the K highest logits before sampling "
                         "(0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling: keep the smallest logit prefix "
                         "with cumulative mass >= P (1.0 = off)")
    ap.add_argument("--engine", choices=["auto", "paged", "dense"],
                    default="auto")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="usable KV pages (default: slots*max_len/page)")
    ap.add_argument("--paged-attn", choices=["kernel", "gather"],
                    default="kernel",
                    help="paged decode attention: in-kernel block-table "
                         "gather (Pallas flash-decode) or the PR-1 dense "
                         "pool gather baseline")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share KV pages across requests with a common "
                         "prompt prefix (radix tree + refcounted "
                         "copy-on-write pages; paged engine only)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decode: verify up to K drafted tokens "
                         "per multi-token step by rejection sampling "
                         "(distribution-preserving at any temperature; "
                         "exact greedy at temperature 0; paged engine only)")
    ap.add_argument("--draft-model", default=None, metavar="ARCH",
                    help="draft with a small second model (any attention-"
                         "only arch; smoke-sized) instead of the built-in "
                         "n-gram prompt lookup; needs --spec-k > 0")
    ap.add_argument("--host-tier", action="store_true",
                    help="two-tier KV: demote idle/preempted pages (and "
                         "recurrent state) to host RAM and promote them "
                         "back through a prefetch stream instead of "
                         "evict + re-prefill (paged engine, single shard)")
    ap.add_argument("--shards", type=int, default=1,
                    help="tensor-parallel shards per engine: KV pools and "
                         "attn/mlp weights shard over a ('data','model') "
                         "mesh of this many devices (paged engine only)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel engine replicas behind a router "
                         "(each replica gets --shards devices; paged "
                         "engine only)")
    ap.add_argument("--route", choices=["hash", "least_loaded"],
                    default="hash", help="replica routing policy")


def build_server(cfg, params, args: argparse.Namespace):
    """The engine for ``cfg`` as the ``add_server_flags`` flags in
    ``args`` describe it. Returns ``(router, eng)``: with --replicas > 1
    the router over the replicas and replica 0 (the telemetry shape
    reference), else ``None`` and the engine."""
    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p).validate()
    drafter = None
    if args.draft_model is not None:
        if args.spec_k <= 0:
            raise SystemExit("--draft-model drafts feed the speculative "
                             "verify step — pass --spec-k > 0 with it")
        if args.draft_model not in ARCHS:
            raise SystemExit(f"--draft-model must be one of {ARCHS}")
        dcfg = get_smoke_config(args.draft_model)
        dparams = api.init_params(dcfg, jax.random.key(1))
        drafter = DraftModelDrafter(dcfg, dparams, max_len=args.max_len,
                                    attn_impl=args.paged_attn)
        print(f"[launch.serve] draft model: {dcfg.name} "
              f"({dcfg.param_count()/1e6:.1f}M params)")
    common = dict(slots=args.slots, max_len=args.max_len, sampling=sampling)
    paged_kw = dict(page_size=args.page_size, num_pages=args.num_pages,
                    attn_impl=args.paged_attn,
                    prefix_cache=args.prefix_cache, spec_k=args.spec_k,
                    drafter=drafter, host_tier=args.host_tier)
    if args.replicas > 1:
        if args.engine == "dense":
            raise SystemExit("--replicas needs the paged engine")
        router = make_replicas(cfg, params, replicas=args.replicas,
                               model=args.shards, policy=args.route,
                               **paged_kw, **common)
        print(f"[launch.serve] router: {args.replicas} replica(s) x "
              f"{args.shards} shard(s) over {len(jax.devices())} "
              f"device(s), policy {args.route}")
        return router, router.engines[0]
    if args.engine == "dense":
        return None, DenseServingEngine(cfg, params, **common)
    mesh = None
    if args.shards > 1:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=args.shards,
                              devices=jax.devices()[:args.shards])
    builder = PagedServingEngine if args.engine == "paged" else ServingEngine
    return None, builder(cfg, params, mesh=mesh, **paged_kw, **common)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--trace", nargs="?", const="", default=None,
                    metavar="TRACE.JSON",
                    help="record per-tick spans and print the per-phase "
                         "wall breakdown; with a filename, also export "
                         "Chrome Trace Event JSON (open in Perfetto)")
    add_server_flags(ap)
    args = ap.parse_args()
    enable_compile_cache()

    # install the tracer BEFORE engine construction: engines capture the
    # process default at init
    tracer = Tracer(enabled=True) if args.trace is not None else None
    if tracer is not None:
        set_default_tracer(tracer)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    print(f"[launch.serve] {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"{args.slots} slots")
    params = api.init_params(cfg, jax.random.key(0))
    router, eng = build_server(cfg, params, args)
    print(f"[launch.serve] engine: {type(eng).__name__}")
    # production-shaped traffic: every request opens with the same system
    # prompt (what --prefix-cache shares), tails vary in length (what the
    # paged engine's buckets absorb)
    sys_prompt = [(5 * j + 2) % cfg.vocab for j in range(2 * args.page_size)]
    reqs = [Request(rid=i,
                    prompt=sys_prompt
                    + [(11 * i + j) % cfg.vocab for j in range(4 + i % 5)],
                    max_new=args.max_new)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    driver = router if router is not None else eng
    done = driver.run_to_completion(reqs, max_steps=5000)
    dt = time.perf_counter() - t0
    toks = sum(len(r.generated) for r in done)
    traces = sum(e.prefill_traces for e in router.engines) \
        if router is not None else eng.prefill_traces
    print(f"[launch.serve] {len(done)}/{len(reqs)} requests, {toks} tokens, "
          f"{toks/dt:.1f} tok/s, {traces} prefill traces")
    if router is not None:
        rs = router.stats()
        print(f"[launch.serve] routed per replica: {rs['routed']}, peak "
              f"pages per replica: "
              f"{[int(p) for p in rs['peak_pages_per_replica']]}, "
              f"preemptions: {rs['preempted']}")
    if isinstance(eng, PagedServingEngine):
        for e_i, e in enumerate(router.engines if router is not None
                                else [eng]):
            ss = e.shard_stats()
            if ss["model_shards"] > 1:
                print(f"[launch.serve] replica {e_i}: "
                      f"{int(ss['model_shards'])} shards "
                      f"({ss['sharded_axes']}), peak "
                      f"{int(ss['peak_pages_per_shard'])} pages/shard, "
                      f"{int(ss['pool_bytes_per_shard'])} pool bytes/shard")
        st = eng.pool_stats()
        print(f"[launch.serve] kv pages: peak {st.peak_pages}/{st.num_pages} "
              f"({st.peak_pages * st.page_size} tokens reserved at peak vs "
              f"{st.dense_equiv_tokens} dense)")
        if eng.has_win:
            print(f"[launch.serve] sliding window ({eng.window} tokens): "
                  f"{eng.win_recycled_pages} pages recycled as they slid "
                  f"out (live window pages per request capped at "
                  f"{eng.win_pages_bound(args.max_len)})")
        if eng.prefix is not None:
            ps = eng.prefix_stats()
            print(f"[launch.serve] prefix cache: hit rate "
                  f"{ps['hit_rate']:.2f}, {ps['shared_token_frac']:.0%} of "
                  f"prompt tokens served from cache, "
                  f"{ps['prefill_tokens_saved']:.0f} prefill tokens saved, "
                  f"{ps['cow_copies']:.0f} CoW copies")
        if eng.tier is not None:
            ts = eng.tier_stats()
            print(f"[launch.serve] host tier: {ts['swap_outs']:.0f} swap-"
                  f"outs / {ts['swap_ins']:.0f} swap-ins, "
                  f"{ts['demoted_pages']:.0f} pages demoted / "
                  f"{ts['promoted_pages']:.0f} promoted, "
                  f"{ts['reprefill_tokens_saved']:.0f} re-prefill tokens "
                  f"saved, prefetch hit rate {ts['prefetch_hit_rate']:.2f}, "
                  f"{ts['host_bytes_peak']:.0f} host bytes at peak")
        if eng.spec_k:
            ss = eng.spec_stats()
            print(f"[launch.serve] speculative (K={eng.spec_k}, drafter "
                  f"{ss['drafter']}): "
                  f"{ss['accepted_per_step']:.2f} tokens/request/step, "
                  f"accept rate {ss['accept_rate']:.2f} "
                  f"({ss['spec_accepted']:.0f}/{ss['spec_drafted']:.0f} "
                  f"drafts)")
            if eng.drafter is not None and eng.drafter.kind == "model":
                ds = eng.drafter.stats()
                print(f"[launch.serve] draft model: "
                      f"{ds['draft_proposed']:.0f} tokens proposed over "
                      f"{ds['draft_decode_calls']:.0f} decode calls, "
                      f"{ds['draft_ingested_tokens']:.0f} tokens ingested, "
                      f"{ds['draft_pool_rejects']:.0f} pool rejects")
    m = eng.metrics()
    sampling = eng.default_params
    if not sampling.is_greedy:
        print(f"[launch.serve] decode policy: temperature "
              f"{sampling.temperature}, top_k {sampling.top_k}, top_p "
              f"{sampling.top_p} — {m['sampling.sampled_tokens']:.0f} "
              f"sampled tokens, "
              f"{m['sampling.step_traces'] + m['sampling.spec_traces']:.0f} "
              f"decode traces (policy-mix invariant)")
    print(f"[launch.serve] latency: ttft p50 {m['latency.ttft_p50_s']:.4f}s "
          f"/ p95 {m['latency.ttft_p95_s']:.4f}s, tpot p50 "
          f"{m['latency.tpot_p50_s']:.4f}s / p95 "
          f"{m['latency.tpot_p95_s']:.4f}s, temporal util "
          f"{m['util.temporal']:.2f}")
    if tracer is not None:
        set_default_tracer(None)
        print("[launch.serve] per-phase wall breakdown (nested spans "
              "overlap their parents):")
        print(tracer.format_phase_walls())
        if args.trace:
            tracer.export(args.trace)
            print(f"[launch.serve] wrote {args.trace}: "
                  f"{len(tracer.events())} events "
                  f"({tracer.dropped_events} dropped) — open in Perfetto "
                  f"(https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
