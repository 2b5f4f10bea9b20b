"""Standing on-chip check: serve full-width models through the normal
entry points on a TPU, and check every generated token against a float32
reference.

  python chip_smoke.py             # one chip: qwen2.5-3b
  python chip_smoke.py --chips 4   # four chips: granite-3-2b, tensor-
                                   # parallel and as router replicas

This is a smoke run, not a benchmark: what it prints says that the program
ran and what it held, never how fast it ran. One process drives every
phase (a chip belongs to one process); each phase raises on a failed
check, so any failure exits non-zero. The last line of stdout is printed
only when every phase passed:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Without a TPU the script stops before any phase runs. Weights are random,
made from ``--seed``; nothing is downloaded. The phase functions also run
on the CPU at smoke size (Pallas in interpret mode): the tests import
them, so the reference check is exercised on every change.

Phases, one chip:
  device     platform, device kind and count, versions, compile cache
  kernel     ops.paged_attention at qwen2.5-3b's decode shape (T=1 and
             the T=4 speculative block) against kernels/ref
  serve      ServingEngine built as repro.launch.serve builds it; 8
             greedy requests, prompts of 300-960 tokens in two prefill
             buckets, 32 new tokens each; the decode program must hold the
             Pallas kernel (tpu_custom_call)
  reference  teacher-forced float32 forward over prompt + generated tokens

Phases, ``--chips 4`` (granite-3-2b: 32 heads / 8 KV heads and d_ff 8192
divide four ways): device, then the same requests served on one chip,
tensor-parallel over four (``--shards 4``) and as four one-chip replicas
behind the router, each run held to the same reference check.
"""
from __future__ import annotations

import argparse
import functools
import importlib.metadata
import json
import sys
import time
import warnings
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.models import api  # noqa: E402
from repro.runtime.serving import Request  # noqa: E402

# |kernel - oracle| on bf16 inputs and a bf16 output (bf16 keeps 8
# significant bits: 2^-8 relative on outputs of magnitude <= ~2)
KERNEL_TOL = 2e-2
# An engine token other than the reference argmax passes only if its
# float32 reference logit is within this much of the argmax's: a near tie
# that bf16 serving may break either way. Sound runs on a TPU v5e read at
# most 0.0408 (qwen2.5-3b, one chip) and 0.0468 (granite-3-2b, TP=4);
# the median top-1/top-2 margin is 0.173, so the runner-up token at a
# typical position sits above this limit.
LOGIT_TOL = 0.1
# At most this share of the generated tokens may be near ties. Sound runs
# read 8/256 (qwen2.5-3b one chip; granite-3-2b TP=4) and 5/256
# (granite-3-2b one chip): a fault that moves tokens to the runner-up
# reads more, even where each stays within LOGIT_TOL.
MAX_NEAR_TIE_SHARE = 0.1
PAGE = 16
MAX_NEW = 32
# one chip: two prefill buckets (512, 1024)
QWEN_PROMPTS = (300, 380, 450, 510, 600, 720, 850, 960)
QWEN_MAX_LEN = 1024
# four chips: one bucket (512), to bound the compiles of six engines
GRANITE_PROMPTS = (260, 300, 340, 380, 420, 450, 480, 500)
GRANITE_MAX_LEN = 576
SLOTS = 8
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Seconds of XLA backend compilation inside the ``with`` block
    (persistent-cache reads included), and persistent-cache hits, from
    JAX's own monitoring events."""

    def __enter__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration

    def _event(self, event, **kwargs):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def device_phase(chips: int) -> dict:
    """The chip, or exit: never carry on on another backend."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {d.platform!r} "
                         f"devices. Nothing was run.")
    if len(devs) < chips:
        raise SystemExit(f"--chips {chips} needs {chips} TPU devices; JAX "
                         f"found {len(devs)}. Nothing was run.")
    versions = ", ".join(f"{name} {importlib.metadata.version(name)}"
                         for name in ("jax", "jaxlib", "libtpu"))
    log(f"device: {d.platform} {d.device_kind} x{len(devs)} ({versions})")
    cache = jax.config.jax_compilation_cache_dir
    on = jax.config.jax_enable_compilation_cache and cache
    log(f"compile cache: {'on, ' + cache if on else 'off'}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def kernel_phase(cfg, *, batch: int, n_blocks: int, seed: int,
                 page: int = PAGE) -> float:
    """ops.paged_attention at ``cfg``'s decode shape against the dense
    oracle, for the plain step (T=1) and a speculative block (T=4).
    Returns the largest error."""
    H, KV, D = cfg.num_heads, cfg.kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(seed)
    n_pages = batch * n_blocks + 1                 # page 0 is scratch
    k_pool = jnp.asarray(rng.standard_normal((n_pages, page, KV, D)),
                         jnp.bfloat16)
    v_pool = jnp.asarray(rng.standard_normal((n_pages, page, KV, D)),
                         jnp.bfloat16)
    table = jnp.asarray(1 + rng.permutation(batch * n_blocks)
                        .reshape(batch, n_blocks), jnp.int32)
    worst = 0.0
    for T in (1, 4):
        q = jnp.asarray(rng.standard_normal((batch, T, H, D)), jnp.bfloat16)
        q = q[:, 0] if T == 1 else q
        lengths = jnp.asarray(rng.integers(T, n_blocks * page + 1, batch),
                              jnp.int32)
        out = ops.paged_attention(q, k_pool, v_pool, table, lengths)
        with jax.default_matmul_precision("highest"):
            ref = ops.ref.paged_attention_ref(q, k_pool, v_pool, table,
                                              lengths)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
        log(f"kernel: paged_attention T={T} B={batch} H={H} KV={KV} D={D} "
            f"{n_blocks}x{page}-token pages: max |kernel - ref| {err!r} "
            f"(tolerance {KERNEL_TOL})")
        if not err <= KERNEL_TOL:
            raise AssertionError(
                f"paged_attention T={T} differs from the oracle by {err}")
        worst = max(worst, err)
    return worst


def make_prompts(cfg, lengths, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).tolist() for n in lengths]


def build_engine(cfg, params, *, slots: int, max_len: int, shards: int = 1,
                 replicas: int = 1):
    """The engine (or router) as ``repro.launch.serve`` builds it, with its
    default flags (greedy, paged, Pallas attention) but for these."""
    ap = argparse.ArgumentParser()
    serve.add_server_flags(ap)
    args = ap.parse_args([f"--slots={slots}", f"--max-len={max_len}",
                          f"--page-size={PAGE}", f"--shards={shards}",
                          f"--replicas={replicas}"])
    router, eng = serve.build_server(cfg, params, args)
    return eng if router is None else router


def serve_phase(server, prompts, *, max_new: int = MAX_NEW):
    """Serve every prompt to completion; every request must finish with
    its whole budget (greedy, no EOS)."""
    reqs = [Request(rid=i, prompt=list(p), max_new=max_new)
            for i, p in enumerate(prompts)]
    done = server.run_to_completion(reqs, max_steps=5000)
    short = [r.rid for r in reqs if len(r.generated) != max_new]
    if len(done) != len(reqs) or short:
        raise AssertionError(f"{len(done)}/{len(reqs)} requests finished; "
                             f"short outputs: {short}")
    return reqs


@functools.partial(jax.jit, static_argnums=0)
def _reference_logits(cfg, params, tokens, positions):
    """Float32 logits at ``positions`` of a forward over ``tokens``. The
    activations are float32 from the embedding on; each bf16 weight is
    upcast inside its own matmul, so no float32 copy of the weight tree
    exists (only of the embedding table). Logits are taken at
    ``positions`` only: (B, n, vocab), never (B, S, vocab)."""
    p = dict(params, embed=params["embed"].astype(jnp.float32))
    h, _, _ = api.forward_hidden(cfg, p, {"tokens": tokens})
    h = jnp.take_along_axis(h, positions[..., None], axis=1)
    w = p["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bnd,dv->bnv", h, w.astype(jnp.float32))
    return logits[..., :cfg.vocab]


def reference_phase(cfg, params, reqs) -> dict:
    """Teacher-forced check of every generated token: run prompt +
    generated tokens through a float32 forward with the same weights, and
    require each engine token to be the reference argmax, or within
    LOGIT_TOL of it in reference logits (a near tie), with at most
    MAX_NEAR_TIE_SHARE of the tokens near ties."""
    B = len(reqs)
    n = max(len(r.generated) for r in reqs)
    seqs = [r.prompt + r.generated[:-1] for r in reqs]
    S = -(-max(len(s) for s in seqs) // 128) * 128
    tokens = np.zeros((B, S), np.int32)
    positions = np.zeros((B, n), np.int32)
    engine_tok = np.zeros((B, n), np.int64)
    valid = np.zeros((B, n), bool)
    for b, (r, s) in enumerate(zip(reqs, seqs)):
        k = len(r.generated)
        tokens[b, :len(s)] = s
        # the token generated k-th is predicted at position len(prompt)-1+k
        positions[b, :k] = len(r.prompt) - 1 + np.arange(k)
        engine_tok[b, :k] = r.generated
        valid[b, :k] = True
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(_reference_logits(
            cfg, params, jnp.asarray(tokens), jnp.asarray(positions)))
    top1 = logits.argmax(-1)
    gap = logits.max(-1) - np.take_along_axis(
        logits, engine_tok[..., None], -1)[..., 0]
    gap = np.where(valid, gap, 0.0)
    near = valid & (engine_tok != top1)
    two = np.partition(logits, -2, axis=-1)[..., -2:]
    margin = two[..., 1] - two[..., 0]
    out = {
        "positions": int(valid.sum()),
        "near_ties": int(near.sum()),
        "max_gap": float(gap.max()),
        "median_margin": float(np.median(margin[valid])),
    }
    log(f"reference: {out['positions']} generated tokens checked, "
        f"{out['near_ties']} near-ties (engine token != float32 argmax), "
        f"largest reference-logit gap below the argmax {out['max_gap']!r} "
        f"(tolerance {LOGIT_TOL}); median top-1/top-2 margin "
        f"{out['median_margin']!r}")
    if out["max_gap"] > LOGIT_TOL:
        b, t = np.unravel_index(int(gap.argmax()), gap.shape)
        raise AssertionError(
            f"request {reqs[b].rid} token {t}: engine chose "
            f"{engine_tok[b, t]}, float32 argmax is {top1[b, t]}, "
            f"{gap[b, t]} logits higher (tolerance {LOGIT_TOL})")
    if out["near_ties"] > MAX_NEAR_TIE_SHARE * out["positions"]:
        raise AssertionError(
            f"{out['near_ties']} of {out['positions']} engine tokens are "
            f"not the float32 argmax (at most {MAX_NEAR_TIE_SHARE:.0%} may "
            f"be near ties)")
    return out


def pool_devices(eng):
    """The set of devices each page-pool leaf of ``eng`` lives on."""
    return [leaf.sharding.device_set for leaf in jax.tree.leaves(eng.cache)]


# ---------------------------------------------------------------------------
# runs
# ---------------------------------------------------------------------------


def serve_one_chip(cfg, params, prompts, *, max_len: int,
                   max_new: int = MAX_NEW, slots: int = SLOTS) -> dict:
    """Serve ``prompts`` on one device, print the smoke lines, and hold
    every generated token to the reference."""
    with CompileClock() as clock:
        eng = build_engine(cfg, params, slots=slots, max_len=max_len)
        reqs = serve_phase(eng, prompts, max_new=max_new)
    has_kernel = "tpu_custom_call" in eng.lower_step().as_text()
    # interpret mode (the CPU) inlines the kernel: only a TPU can hold it
    if jax.default_backend() == "tpu" and not has_kernel:
        raise AssertionError("the decode program holds no Pallas kernel "
                             "(no tpu_custom_call in its StableHLO)")
    st = eng.pool_stats()
    mem = jax.devices()[0].memory_stats() or {}
    lengths = [len(p) for p in prompts]
    log("smoke run, not a benchmark:")
    log(f"  engine: {type(eng).__name__}; decode program holds the Pallas "
        f"kernel (tpu_custom_call): {has_kernel}")
    log(f"  compile seconds: {clock.seconds!r} "
        f"({clock.cache_hits} persistent-cache hits)")
    log(f"  tokens served: {sum(len(r.generated) for r in reqs)} "
        f"({len(reqs)} requests, prompts {min(lengths)}-{max(lengths)} "
        f"tokens, {eng.prefill_traces} prefill buckets)")
    log(f"  peak_bytes_in_use: {mem.get('peak_bytes_in_use')}")
    log(f"  kv pages used: peak {st.peak_pages} of {st.num_pages} "
        f"({st.page_size}-token pages)")
    return reference_phase(cfg, params, reqs)


def serve_four_ways(cfg, params, prompts, *, max_len: int,
                    max_new: int = MAX_NEW, slots: int = SLOTS) -> None:
    """The same requests on one device, tensor-parallel over four
    (``--shards 4``) and as four one-device replicas behind the router;
    each run is held to the reference check on its own tokens."""
    kw = dict(slots=slots, max_len=max_len)
    devs = jax.devices()[:4]
    total = len(prompts) * max_new

    with CompileClock() as clock:
        base = serve_phase(build_engine(cfg, params, **kw), prompts,
                           max_new=max_new)
    log(f"one device: compile seconds {clock.seconds!r}")
    reference_phase(cfg, params, base)

    with warnings.catch_warnings():
        # a replicated attention or ffn axis is a failed TP plan here
        warnings.filterwarnings("error", message=".*replicat")
        eng = build_engine(cfg, params, shards=4, **kw)
    axes = set(eng.shard_stats()["sharded_axes"].split("+"))
    if not {"heads", "kv_heads", "ffn"} <= axes:
        raise AssertionError(f"TP=4 sharded only {sorted(axes)}")
    spread = [len(s) for s in pool_devices(eng)]
    if any(n != 4 for n in spread):
        raise AssertionError(f"TP=4 pool leaves span {spread} devices")
    with CompileClock() as clock:
        tp = serve_phase(eng, prompts, max_new=max_new)
    del eng
    log(f"TP=4: sharded axes {sorted(axes)}; every pool leaf spans 4 "
        f"devices; compile seconds {clock.seconds!r}; "
        f"{_agreement(base, tp)} of {total} tokens equal to one device")
    reference_phase(cfg, params, tp)

    router = build_engine(cfg, params, replicas=4, **kw)
    for i, e in enumerate(router.engines):
        sets = pool_devices(e)
        if any(s != {devs[i]} for s in sets):
            raise AssertionError(f"replica {i} pool is on {sets[0]}, not "
                                 f"{{{devs[i]}}}")
    with CompileClock() as clock:
        rep = serve_phase(router, prompts, max_new=max_new)
    log(f"router: replica i's pool on device {[d.id for d in devs]}[i], "
        f"routed {router.stats()['routed']}; compile seconds "
        f"{clock.seconds!r}; {_agreement(base, rep)} of {total} tokens "
        f"equal to one device")
    reference_phase(cfg, params, rep)


def _agreement(a, b) -> int:
    return sum(x == y for ra, rb in zip(a, b)
               for x, y in zip(ra.generated, rb.generated))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: qwen2.5-3b on one chip (the default); 4: "
                         "granite-3-2b tensor-parallel and as router "
                         "replicas, against one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    device = device_phase(args.chips)
    t0 = time.perf_counter()
    cfg = get_config("granite-3-2b" if args.chips == 4 else "qwen2.5-3b")
    params = jax.block_until_ready(
        api.init_params(cfg, jax.random.key(args.seed)))
    log(f"params: {cfg.name}, {cfg.param_count() / 1e9:.3f}B, "
        f"{cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads}/{cfg.kv_heads} heads, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab}, {cfg.dtype}; made in "
        f"{time.perf_counter() - t0:.1f} s")
    if args.chips == 4:
        serve_four_ways(cfg, params,
                        make_prompts(cfg, GRANITE_PROMPTS, args.seed),
                        max_len=GRANITE_MAX_LEN)
    else:
        kernel_phase(cfg, batch=SLOTS, n_blocks=QWEN_MAX_LEN // PAGE,
                     seed=args.seed)
        serve_one_chip(cfg, params,
                       make_prompts(cfg, QWEN_PROMPTS, args.seed),
                       max_len=QWEN_MAX_LEN)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
