"""Training launcher: ``--arch`` selects any assigned architecture.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
      --steps 30 [--ckpt-dir /tmp/ckpt] [--grad-accum 2] [--compress-grads]

``--smoke`` runs the reduced same-family config on local devices; without
it, the full config is used (real-hardware path; on CPU it will OOM —
that is what the dry-run is for).
"""
from __future__ import annotations

import argparse

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.data.synthetic import DataConfig, SyntheticDataset
from repro.launch.mesh import make_host_mesh
from repro.optim import adamw
from repro.parallel.sharding import NO_RULES, Rules
from repro.runtime.trainer import Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=0)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    print(f"[launch.train] {cfg.name}: {cfg.param_count()/1e6:.1f}M params")
    if args.model_parallel > 1:
        mesh = make_host_mesh(model=args.model_parallel)
        rules = Rules(mesh)
        ctx = jax.set_mesh(mesh)
    else:
        rules, ctx = NO_RULES, None

    ds = SyntheticDataset(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                     global_batch=args.batch))
    opt = adamw.AdamWConfig(lr=3e-3, warmup_steps=max(5, args.steps // 10),
                            total_steps=args.steps,
                            moment_dtype=cfg.moment_dtype)

    def run():
        tr = Trainer(cfg, opt, ds, rules=rules, ckpt_dir=args.ckpt_dir,
                     save_every=args.save_every, grad_accum=args.grad_accum,
                     compress_grads=args.compress_grads, log_every=10)
        tr.run(args.steps)
        return tr

    if ctx is not None:
        with ctx:
            tr = run()
    else:
        tr = run()
    print(f"[launch.train] done at step {tr.step}; "
          f"{tr.monitor.slow_steps} straggler-flagged steps")


if __name__ == "__main__":
    main()
