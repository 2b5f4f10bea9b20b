"""Run one benchmark cell on the chip and print its result line.

  python3 bench/run.py --workload qwen2.5-3b.chat --seed 7 --seconds 50 \
      --trace 0

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``: the published model, the program's
preset, the deployment and the reference module that checks and counts
the architecture, ``bench/reference/__init__.py``) and a traffic mix
(``bench/traffic/<mix>.json``, read by ``bench/traffic.py``). The
deployment's ``flags`` are ``launch/serve.add_server_flags`` flags by
name: ``{"num_pages": 4096, "prefix_cache": true}`` is
``--num-pages=4096 --prefix-cache``. One run, one process:

  1. the persistent compile cache at ``<checkout>/.jax_cache``; the
     configuration's reference module, loaded from its ``reference``
     path;
  2. weights drawn from ``--seed`` on the device (``bench/weights.py``,
     with the reference's rules for leaves of its own);
  3. the engine, or the router over replicas, as
     ``repro.launch.serve.build_server`` builds it from those flags;
  4. warm-up: on every engine, one request per prefill bucket the
     schedule reaches, run to completion (every program compiled or
     loaded from the cache); then, in an open loop, the mix's
     ``lead_s`` of arrivals, so the window opens on a steady state;
  5. the window: ``--seconds`` of traffic through the scheduler
     (``bench/loop.py``); with ``--trace 1`` all of it under the JAX
     profiler;
  6. the check: a sample of the served requests against the plain float32
     reference (``bench/check.py``), after the engine's state is freed;
  7. the metrics: each from its reader, ``bench/metrics/<metric>.py``,
     or for ``<metric>.<part>`` with no reader of its own,
     ``<metric>.py`` (``--trace 0``: the cell's end-to-end metrics;
     ``--trace 1``: its per-layer metrics).

The last line of stdout is one JSON object (correct, attempted, failed,
metrics, device[, breakdown], checks). Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import check, loop, traffic, weights, xplane  # noqa: E402
from bench.readers import Run  # noqa: E402

BENCH_DIR = ROOT / "bench"
WARM_NEW = 3                       # tokens per warm-up request
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# what a reference module provides (bench/reference/__init__.py)
REFERENCE_API = ("Spec", "logits_at", "Q_CHUNK", "plain", "counts")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str):
    spec = load_json(ROOT / "BENCHMARK.json")
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    return spec, cell, load_json(ROOT / conf["file"])


def metrics_for(spec: dict, cell: dict, trace: bool) -> list:
    """The cell's end-to-end metrics (``trace`` off) or per-layer ones."""
    group = spec["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if cell["name"] in m.get("workloads", [cell["name"]])]


def load_module(path: Path, name: str):
    """The Python file ``path`` as the module ``name``, executed once per
    process and registered in ``sys.modules`` (a dataclass resolves its
    annotations there)."""
    if name not in sys.modules:
        sp = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(sp)
        sys.modules[name] = mod
        try:
            sp.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def read_metric(name: str, run: Run):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    if not path.exists():            # e.g. prefill_ms_per_ktok.longdoc
        path = BENCH_DIR / "metrics" / f"{name.split('.')[0]}.py"
    return load_module(path, f"metric_{path.stem}").read(run)


def load_reference(conf: dict):
    """The reference module that the configuration's ``reference`` key
    names (a path from the checkout's root): it checks the served tokens
    and counts the work. Exits with a message where the key, the file or
    one of ``REFERENCE_API`` is missing."""
    rel = conf.get("reference")
    if not rel:
        raise SystemExit("the configuration names no reference module "
                         "(key 'reference')")
    path = ROOT / rel
    if not path.is_file():
        raise SystemExit(f"no reference module at {rel!r}")
    mod = load_module(path, f"reference:{rel}")
    missing = [a for a in REFERENCE_API if not hasattr(mod, a)]
    if missing:
        raise SystemExit(f"reference module {rel!r} lacks "
                         f"{', '.join(missing)}")
    return mod


def chips(n: int) -> dict:
    """The TPU this run measures, or exit: never another backend."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise SystemExit(f"this cell needs {n} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform!r} device(s). "
                         f"Nothing was run.")
    return device_info(n)


def device_info(n: int) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": n}


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, for
    every program however quick to compile."""
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def program_config(conf: dict):
    from repro.configs import get_config
    prog = conf["program"]
    return dataclasses.replace(get_config(prog["preset"]), **prog["changes"])


def server_flags(flags: dict) -> list:
    """``add_server_flags`` arguments from a JSON object of them."""
    out = []
    for key, v in flags.items():
        flag = "--" + key.replace("_", "-")
        if v is True:
            out.append(flag)
        elif v is not False:
            out.append(f"{flag}={v}")
    return out


def build_server(cfg, params, flags: dict):
    """What ``build_server`` builds for ``flags``: the router over the
    replicas, or the one engine."""
    from repro.launch import serve
    ap = argparse.ArgumentParser()
    serve.add_server_flags(ap)
    args = ap.parse_args(server_flags(flags))
    router, eng = serve.build_server(cfg, params, args)
    return eng if router is None else router


class CompileCount:
    """Backend compiles (persistent-cache loads included) while open."""

    def __init__(self):
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **kw):
        if self.on and event == BACKEND_COMPILE:
            self.n += 1


def warm_up(eng, lengths, vocab: int) -> None:
    """One request per prefill bucket that ``lengths`` reach, served to
    completion on ``eng`` through the scheduler, so that no program first
    runs in the window."""
    buckets = sorted({eng._bucket(n) for n in lengths})
    drv = loop.Driver(eng)
    page = eng.page_size
    for b in buckets:
        # one short of a page boundary, so decode grows the block table
        n = min(b, eng.max_len - WARM_NEW - 2) // page * page - 1
        item = traffic.Item(-1, [(7 * j + b) % vocab for j in range(n)],
                            WARM_NEW)
        drv.add(item, loop.clock())
    while drv.busy():
        drv.tick()
    log(f"warm-up: prefill buckets {buckets}")


class Tracer:
    """The JAX profiler over the whole window. Starting it stalls the
    host for seconds, so it starts in set-up; the ``window`` span opens
    with the window, and the profiler stops as the window closes."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0          # host spans only, no calls
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = self.stop_at = float("inf")
        self.host = None
        self._ann = None
        self._done = False

    def window(self, t0: float, seconds: float) -> None:
        self.t0, self.stop_at = t0, t0 + seconds

    def __call__(self, now: float) -> None:
        """Once per loop iteration: open the ``window`` span at ``t0``,
        stop the profiler at the window's close."""
        if now >= self.stop_at:
            self.close()
        elif now >= self.t0 and self._ann is None and not self._done:
            self._ann = jax.profiler.TraceAnnotation(xplane.WINDOW)
            self._ann.__enter__()
            self.h0 = loop.clock()

    def close(self) -> None:
        if self._done:
            return
        self._done = True
        if self._ann is not None:
            self.host = (self.h0, loop.clock())
            self._ann.__exit__(None, None, None)
            self._ann = None
        jax.profiler.stop_trace()


def run_cell(cell: dict, conf: dict, mix: dict, *, cfg, seed: int,
             seconds: float, trace: bool, rate=None, metrics=(),
             peaks=None, keep_trace=None, engine_hook=None,
             control=False):
    """Everything after the chip check: (result, Run). ``cfg`` is the
    program's config; ``rate`` overrides the mix's (the knee sweep);
    ``engine_hook`` (tests) may break the engine before the window;
    ``control`` also reads the lower-precision references."""
    ref = load_reference(conf)
    flags = conf["deployment"]["flags"]
    model = conf["model"]
    t = loop.clock()
    params = weights.make(cfg, seed, getattr(ref, "WEIGHT_RULES", None))
    log(f"weights: {cfg.name} made in {loop.clock() - t:.2f} s")
    server = build_server(cfg, params, flags)
    engines = getattr(server, "engines", [server])
    max_len = engines[0].max_len
    if engine_hook is not None:
        engine_hook(server)
    vocab = model["vocab_size"]
    if mix["loop"] == "open":
        items = traffic.open_schedule(mix, seed, seconds, vocab, rate=rate)
        lengths = [len(i.prompt) for i in items]
    else:
        deck = traffic.ClosedDeck(mix, seed, vocab)
        lengths = deck.prompt_lengths()
    for e in engines:
        warm_up(e, lengths, vocab)
    drv = loop.Driver(server)
    first = []
    if mix["loop"] == "closed":
        now = loop.clock()
        first = [drv.add(i, now) for i in deck.first_wave]
        while drv.pending:
            drv.tick()
    compiles = CompileCount()
    tracer = Tracer() if trace else None
    # an open loop's arrivals start lead_s before the window opens at t0
    t0 = loop.clock() + traffic.lead_s(mix)
    if tracer is not None:
        tracer.window(t0, seconds)
        drv.on_iter = tracer
    compiles.on = True
    if mix["loop"] == "open":
        drv.run_open(items, t0, seconds)
    else:
        drv.run_closed(deck, t0, seconds, first)
    compiles.on = False
    drained = loop.clock()
    setup_s = t0 - T_PROCESS
    if tracer is not None:
        tracer.close()
    # the peak on the fullest chip the cell uses
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices()[:cell.get("chips", 1)])
    recs = list(drv.log.recs.values())
    log(f"window: {seconds} s after {traffic.lead_s(mix)} s of lead, "
        f"{len(recs)} requests, "
        f"{len(drv.log.steps)} decode steps, {len(drv.log.prefills)} "
        f"prefills, {compiles.n} compiles from the lead on, "
        f"{drv.preempted} preempted")
    late = [r.added - r.due for r in recs if r.added is not None]
    if late:
        log(f"generator lateness: max {max(late)!r} s, mean "
            f"{sum(late) / len(late)!r} s")

    run = Run(setup_s=setup_s, t0=t0, end=t0 + seconds, drained=drained,
              log=drv.log, counts=ref.counts(model),
              peaks=peaks or {})
    if tracer is not None:
        if keep_trace:
            shutil.copytree(tracer.dir, keep_trace, dirs_exist_ok=True)
        run.trace = xplane.read(xplane.find(tracer.dir))
        run.traced = tracer.host
        shutil.rmtree(tracer.dir, ignore_errors=True)
    # free the program's state before the reference runs
    del drv, server, engines, first
    gc.collect()

    spec = ref.Spec.from_model(model)
    w = ref.plain(params)
    sample = check.sample(recs, seed)
    t = loop.clock()
    numbers = check.compare(
        ref, spec, w, [(r.req.prompt, r.req.generated) for r in sample],
        max_len=max_len, max_out=mix["output_tokens"]["max"],
        control=control)
    ok, rows = check.verdict(numbers, conf["check"])
    log(f"check: {int(numbers['tokens'])} served tokens of "
        f"{len(sample)} requests against the float32 reference in "
        f"{loop.clock() - t:.1f} s: {numbers}")

    if mix["loop"] == "open":
        attempted = run.due_in_window()
        failed = [r for r in attempted if not r.stamps]
    else:
        attempted = [r for r in recs if r.added <= run.end]
        failed = [r for r in attempted if r.finished and not r.stamps]
    out = {"correct": ok, "attempted": len(attempted),
           "failed": len(failed), "metrics": {}}
    for m in metrics:
        v = read_metric(m["name"], run)
        if v is not None:
            out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    out["device"] = dict(device_info(cell.get("chips", 1)),
                         memory_peak_bytes=mem_peak)
    if run.trace is not None:
        out["device"].update(busy_s=run.trace.busy_s,
                             window_s=run.trace.window_s)
        out["breakdown"] = {"device_ops": run.trace.top_ops(),
                            "idle_gaps": run.trace.idle_gaps()}
    out["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    out["numbers"] = numbers
    for k, v, lim in rows:
        log(f"check {k} {v!r} limit {lim!r}")
    return out, run


def prepare(workload: str):
    """The set-up every run on the chip shares (this script's and
    ``calibrate.py``'s): the cell, its configuration and mix, the chip
    check, the chip's peaks, the compile cache."""
    spec, cell, conf = load_cell(workload)
    device = chips(cell["chips"])
    peaks = load_json(BENCH_DIR / "peaks.json")
    if device["kind"] not in peaks:
        raise SystemExit(f"no peaks for device kind {device['kind']!r} in "
                         f"bench/peaks.json")
    enable_compile_cache()
    return spec, cell, conf, traffic.load_mix(cell["traffic"]), \
        peaks[device["kind"]]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler trace of a --trace 1 run here")
    args = ap.parse_args(argv)
    spec, cell, conf, mix, peaks = prepare(args.workload)
    out, _ = run_cell(cell, conf, mix, cfg=program_config(conf),
                      seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace),
                      metrics=metrics_for(spec, cell, bool(args.trace)),
                      peaks=peaks, keep_trace=args.keep_trace)
    out.pop("numbers")              # leaves "checks" the last key
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
