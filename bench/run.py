#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no children. Refuses to start without a TPU (no CPU fallback,
no analytic number). Sets up (weights on the device from the seed, the
cell's own shapes warmed, the reference check), measures for `--seconds`,
drains, and prints as the LAST line of standard output one JSON object:
correct, attempted, failed, metrics, device (and breakdown when traced).
Everything else worth reading is printed on earlier lines.

`--rehearse` debugs this file on a CPU at a tiny size with the kernels
interpreted: output headed REHEARSAL, exit code 3, never a result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path[0] != ROOT:
    sys.path.insert(0, ROOT)  # this checkout's bigdl_tpu and bench, no other

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def say(msg: str = "") -> None:
    print(msg, flush=True)


def die(msg: str, code: int = 2):
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def device_info(chips: int, rehearse: bool) -> dict:
    import importlib.metadata as md

    import jax

    backend = jax.default_backend()
    if backend != "tpu" and not rehearse:
        die(f"JAX found no TPU (default backend is {backend!r}). The "
            "benchmark runs on the chip only.")
    devs = jax.devices()
    if len(devs) < chips and not rehearse:
        die(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say(f"device: platform={info['platform']} device_kind={info['kind']!r} "
        f"count={info['count']}")

    def version(pkg):
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return "not installed"

    say(f"versions: python {sys.version.split()[0]}, jax {version('jax')}, "
        f"jaxlib {version('jaxlib')}, libtpu {version('libtpu')}")
    return info


def memory_peak(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:max(chips, 1)]]
    return int(max(peaks))


class Profiler(threading.Thread):
    """Traces `seconds` of the window from `at` seconds in, in a thread of
    its own so that the load generator is not held up. One
    `TraceAnnotation` at a known instant of the benchmark's clock aligns
    the profile's clock with the spans'."""

    NAME = "bench_sync"

    def __init__(self, clock, logdir, at, seconds):
        super().__init__(daemon=True)
        self.clock, self.logdir = clock, logdir
        self.at, self.seconds = at, seconds
        self.t_begin = self.t_sync = self.t_end = None
        self.error = None
        self.go = threading.Event()
        self.t0 = None

    def run(self):
        import jax

        self.go.wait()
        try:
            time.sleep(max(self.t0 + self.at - self.clock(), 0))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
            self.t_begin = self.clock()
            with jax.profiler.TraceAnnotation(self.NAME):
                self.t_sync = self.clock()
                time.sleep(0.001)
            time.sleep(self.seconds)
            self.t_end = self.clock()
            jax.profiler.stop_trace()
        except Exception as e:  # reported by the main thread
            self.error = f"{type(e).__name__}: {e}"


def prepare(cell, seed: int, trace: bool, rehearse: bool):
    """Everything before the window: device, compile cache, weights made on
    the device, the entry's driver, warm-up of the cell's own shapes and the
    reference check. Returns a namespace; `bench/tools/sweep.py` shares it."""
    import types

    from bench import cells, costs, weights
    from bench.records import CompileLog

    info = device_info(cell.chips, rehearse)
    import jax

    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.ops.routes import record_routes
    from bigdl_tpu.utils.compile_cache import enable_compile_cache

    clock = time.perf_counter
    cache_dir = enable_compile_cache()
    log = CompileLog(clock)
    peak = None if rehearse else costs.peaks(info["kind"])

    if rehearse:
        cell.config = merge(cell.config, cell.config["bench"].get("rehearsal"))
        cell.traffic = merge(cell.traffic, cell.traffic.get("rehearsal"))
    hf, qtype = cells.as_run(cell.config), cell.config["bench"]["qtype"]
    model_cfg = ModelConfig.from_hf_config(hf)

    t = clock()
    params = jax.block_until_ready(
        weights.make_params(model_cfg, seed, qtype))
    weights_s = clock() - t
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    say(f"weights: {nbytes / 2**30:.2f} GiB packed ({qtype}) made on the "
        f"device from seed {seed} in {weights_s:.1f} s")
    model = TpuModel(model_cfg, params, qtype)

    tracer = None
    if trace:
        from bigdl_tpu.obs.tracing import TraceRecorder

        tracer = TraceRecorder(capacity=1 << 20, clock=clock)
    generator = cell.generator()
    with record_routes() as routes:
        driver = cell.entry().Driver(cell, model, clock, tracer=tracer)
        t = clock()
        driver.warm(generator.shapes(cell.traffic))
        warm_s = clock() - t
        t = clock()
        ref_ok, ref_msg = driver.check(cell, hf, params, seed)
        check_s = clock() - t
    say(f"warm-up {warm_s:.1f} s; reference check {check_s:.1f} s: {ref_msg}")
    say("routes traced in set-up (count  op  route  detail):")
    for (op, route, detail), n in sorted(routes.items()):
        say(f"  {n:3d}  {op:9s} {route:22s} {detail}")
    if tracer is not None:
        tracer.clear()
    t_ready = clock()
    setup = {"setup_s": t_ready - T_START, "weights_s": weights_s,
             "warm_s": warm_s, "check_s": check_s,
             "compile_s": log.seconds(t1=t_ready)}
    ev = {k: log.count(k, t1=t_ready) for k in
          ("compile_requests_use_cache", "cache_hits", "cache_misses")}
    say(f"set-up {setup['setup_s']:.1f} s: weights {weights_s:.1f}, warm-up "
        f"{warm_s:.1f}, check {check_s:.1f}; JAX's compile log (trace, "
        f"lower, compile or load) sums to {setup['compile_s']:.1f} s; "
        f"compile cache {cache_dir}: {ev['compile_requests_use_cache']} "
        f"requests, {ev['cache_hits']} hits, {ev['cache_misses']} misses")
    for name, s in log.by_program(1.0):
        say(f"  {s:7.2f} s  {name}")
    return types.SimpleNamespace(
        info=info, clock=clock, log=log, peak=peak, hf=hf, params=params,
        tracer=tracer, generator=generator, driver=driver, ref_ok=ref_ok,
        setup=setup, weight_bytes=costs.tree_bytes(params))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, kernels interpreted; exit "
                         "code 3, never a result")
    args = ap.parse_args(argv)

    if not os.path.isfile(os.path.join(ROOT, "bigdl_tpu", "__init__.py")):
        die(f"no bigdl_tpu package in {ROOT}: nothing to measure")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["BIGDL_TPU_PALLAS"] = "interpret"
        say("REHEARSAL: CPU, tiny sizes, kernels through the Pallas "
            "interpreter. Nothing below is a result.")

    from bench import cells, stats
    from bench.records import Run

    try:
        cell = cells.resolve(args.workload, ROOT)
    except (KeyError, FileNotFoundError) as e:
        die(str(e))
    say(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, entry {cell.entry_name}, chips {cell.chips}, "
        f"seed {args.seed}, {args.seconds} s, trace {args.trace}")

    p = prepare(cell, args.seed, bool(args.trace), args.rehearse)
    clock, log, driver, tracer = p.clock, p.log, p.driver, p.tracer
    plan = p.generator.plan(cell.traffic, args.seed, args.seconds,
                            p.hf["vocab_size"])

    prof = None
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        t_s = min(float(cell.traffic.get("trace_seconds", 3.0)),
                  args.seconds * 0.5)
        prof = Profiler(clock, TRACE_DIR, at=args.seconds * 0.3, seconds=t_s)
        prof.start()
        prof.t0 = clock()
        prof.go.set()

    # ---- the window -------------------------------------------------------
    try:
        t0, t1, reqs, extra = driver.run(plan, args.seconds)
    except BaseException:
        driver.finish()  # stop the threads; the error is what is reported
        raise
    if prof is not None:
        prof.join(timeout=300)
    problems = driver.finish()

    run = Run(cell=cell, hf=p.hf, peak=p.peak, t0=t0, t1=t1, requests=reqs,
              spans=tracer.events() if tracer is not None else [],
              setup=p.setup, compile_log=log, weight_bytes=p.weight_bytes,
              extra=extra)

    device = dict(p.info, memory_peak_bytes=memory_peak(cell.chips))
    breakdown = None
    if prof is not None:
        if prof.error or prof.is_alive():
            problems.append(f"profiler: {prof.error or 'did not stop'}")
        else:
            from bench.reduce import xplane

            run.device = xplane.reduce_dir(TRACE_DIR, Profiler.NAME,
                                           prof.t_sync, prof.t_begin,
                                           prof.t_end)
            mid = (prof.t_begin + prof.t_end) / 2
            run.extra["live_tokens_in_trace"] = sum(
                r.n_prompt + sum(1 for s in r.stamps if s <= mid)
                for r in reqs if r.stamps and r.stamps[0] <= mid
                and (not r.done or r.stamps[-1] >= mid))
            device["busy_s"] = run.device.busy_s
            device["window_s"] = run.device.window_s
            breakdown = {
                "device_ops": run.device.top_ops(10),
                "idle_gaps": run.device.idle_gaps(
                    xplane.make_labeller(run.spans, reqs), 10)}
            for plane, lines in run.device.loaded.lines.items():
                say(f"trace plane {plane}: {lines}")
            say(f"trace: {run.device.window_s:.3f} s traced, device busy "
                f"{run.device.busy_s:.3f} s; {run.device.summary()}")

    failed = [r for r in reqs if r.failed]
    for r in failed[:5]:
        say(f"failed request: {r.why}")
    for msg in problems:
        say(f"PROBLEM: {msg}")
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = cell.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    compiles = log.count("cache_misses", t0, t1)
    late = [(r.t_sent - r.t_due) * 1e3 for r in reqs if r.t_due is not None]
    if late:  # an open loop, for the reader: no metric, bounds nothing
        say(f"time to first token from the due time, p50 / p90: "
            f"{stats.ttft_percentile_ms(run, 50):.1f} / "
            f"{stats.ttft_percentile_ms(run, 90):.1f} ms; the load "
            f"generator submitted {stats.percentile(late, 95):.1f} ms after "
            f"the due time at the p95 (a starved generator must not be read "
            f"as a fast server)")
    say(f"window: {len(reqs)} attempted, {len(failed)} failed, drained in "
        f"{extra.get('drain_s', 0.0):.1f} s; programs XLA compiled inside "
        f"the window (persistent-cache misses; every shape is warmed in "
        f"set-up, so 0 is expected): {compiles}")
    result = {
        "correct": bool(p.ref_ok and not problems
                        and not any(r.wrong for r in reqs)),
        "attempted": len(reqs), "failed": len(failed),
        "metrics": metrics, "device": device,
        "compiles_in_window": compiles}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse:
        say("REHEARSAL complete, not a result: " + json.dumps(result))
        return 3
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
