"""Runs one benchmark cell once, on the chip, and prints its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout.  The cell's configuration, traffic mix,
gateway sizing and limits are read from benchmarks/chip/{cells,configs,
traffic}/ by the names in BENCHMARK.json; each metric has a reader in
benchmarks/chip/metrics/.  The run:

  set-up   draws the weights from the seed on the device in one compiled
           call, builds the gateway (which seals them into their own
           buffers), compiles or loads from the cache the cell's programs
           with one request, runs the pool's bookkeeping at every page
           count the stream will ask for, and runs the cell's own traffic
           for ``warm_s`` seconds so that the batch is at its steady state;
  window   ``--seconds`` of the same stream through SecureGateway.submit /
           step; with ``--trace 1`` the profiler records its first
           ``trace_s`` seconds;
  check    once the window has closed and the gateway is freed, the plain
           reference recomputes a sample of the finished requests from the
           seed (check.py) and ``correct`` says whether every served token
           lies within the cell's limit of the reference's best.

The last stdout line is one JSON object: correct, attempted, failed,
metrics (end-to-end with --trace 0, per-layer with --trace 1), device, with
--trace 1 breakdown, and last the compared numbers with their limits
("checks"), which also end stderr.  Without a TPU, or with fewer chips than
the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def log(*a) -> None:
    print(*a, flush=True)


def fail(msg: str) -> None:
    print(f"run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def _compile_counter():
    """Counts programs lowered from now on (each new shape or program)."""
    from jax import monitoring
    box = {"on": False, "n": 0}
    lowered = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def listener(event, _duration, **_kw):
        if box["on"] and event == lowered:
            box["n"] += 1
    monitoring.register_event_duration_secs_listener(listener)
    return box


def warm_programs(gw, cell, seed: int) -> None:
    """One request that runs every program the window uses at its one
    shape: a chunk prefill [slots, chunk], decode [slots], and a page
    close (the decode crosses a page boundary)."""
    import numpy as np
    g = cell.gateway
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    prompt = rng.integers(0, gw.cfg.vocab, g["prefill_chunk"], dtype=np.int32)
    gw.submit("warm", prompt, max_new=g["page_size"] + 2)
    gw.drain()


def warm_pool_shapes(gw, mix: dict, page_size: int) -> None:
    """The pool brands and frees a request's pages with eager array updates
    whose shapes follow its page count: run them once at every count the
    mix's lengths allow, so none compiles inside the window, and every
    seed's set-up does the same work (after a checkout's first run, all
    of it from the compile cache)."""
    lo = -(-(mix["prompt"]["min"] + mix["output"]["min"]) // page_size)
    hi = -(-(mix["prompt"]["max"] + mix["output"]["max"]) // page_size)
    key = gw.sessions.channel("warm").key_words
    for n in range(lo, hi + 1):
        pages = gw.pool.alloc(n, "warm", key, [0] * n)
        gw.pool.free(pages)


def serve(cell, seed: int, seconds: float, trace: bool,
          t_process: float = T_PROCESS, trace_dir=TRACE_DIR):
    """Set up and run the window; -> everything the metrics and the check
    read, with the gateway freed."""
    import jax
    from repro.serve import SecureGateway

    from benchmarks.chip import load, spec, traffic, weights
    from benchmarks.chip import trace as trace_lib

    dev = jax.devices()[0]
    cfg = spec.model_config(cell.config)
    g, mix, c = cell.gateway, cell.mix, cell.cell
    loop = mix["loop"]
    rate = c.get("rate_per_s")
    clients = g["slots"] if loop == "closed" else 0
    n_req = traffic.stream_length(mix, c["warm_s"] + seconds + 30, rate,
                                  clients)
    stream = traffic.generate(mix, seed, cfg.vocab, n_req, rate)

    gw = SecureGateway(cfg, weights.generate(cfg, seed),
                       security=cell.security, max_slots=g["slots"],
                       page_size=g["page_size"], n_pages=g["n_pages"],
                       max_pages_per_seq=g["max_pages_per_seq"],
                       prefill_chunk=g["prefill_chunk"], donate_params=True)
    t_built = time.perf_counter()
    warm_programs(gw, cell, seed)
    t_programs = time.perf_counter()
    warm_pool_shapes(gw, mix, g["page_size"])
    t_compiled = time.perf_counter()
    drv = load.Load(gw, stream, loop, clients=clients)
    t_stream = time.perf_counter()
    drv.start(t_stream)
    drv.run_until(t_stream + c["warm_s"])
    compiles = _compile_counter()
    gw.reset_metrics()
    w0 = time.perf_counter()
    w1 = w0 + seconds
    compiles["on"] = True
    traced = []
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        i0 = len(drv.steps)
        jax.profiler.start_trace(str(trace_dir))
        with jax.profiler.TraceAnnotation(trace_lib.WINDOW):
            drv.run_until(min(w1, w0 + c["trace_s"]))
        jax.profiler.stop_trace()
        traced = drv.steps[i0:]
    drv.run_until(w1)
    compiles["on"] = False
    peak_bytes = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    counters = gw.metrics()
    drv.close(w1)
    log(f"set-up: build {t_built - t_process:.3f} s, programs "
        f"{t_programs - t_built:.3f} s, pool shapes "
        f"{t_compiled - t_programs:.3f} s, warm traffic "
        f"{w0 - t_stream:.3f} s")
    log(f"window {w1 - w0:.3f} s: {counters['steps']} steps, "
        f"{counters['prefill_chunks']} with a prefill chunk, "
        f"{counters['decode_tokens']} decode tokens; compiles inside the "
        f"window {compiles['n']}; peak_bytes_in_use {peak_bytes}")
    drv.gw = None
    del gw
    gc.collect()
    run = types.SimpleNamespace(
        cell=cell, cfg=cfg, seed=seed, trace=None, window=(w0, w1),
        setup_s=w0 - t_process, due=drv.due, steps=drv.steps,
        traced_steps=traced, counters=counters, model=cell.config["model"],
        slots=g["slots"], peak=None,
        device={"platform": dev.platform, "kind": dev.device_kind,
                "count": len(jax.devices()),
                "memory_peak_bytes": peak_bytes})
    _log_traffic(run, loop)
    if trace:
        files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
        run.trace = trace_lib.reduce(files[-1])
        run.peak = spec.peaks(dev.device_kind, cell.data)
        log(f"trace: {files[-1].stat().st_size} bytes; programs "
            + ", ".join(f"{k} x{v[0]} {v[1] * 1e-6:.3f} ms"
                        for k, v in sorted(run.trace.programs.items())))
    return run


def judge(run) -> dict:
    """The result object: metrics, and the served tokens checked against
    the reference."""
    from benchmarks.chip import check, spec
    cell, (w0, w1) = run.cell, run.window
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
              "device": dict(run.device)}
    if run.trace is not None:
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.top_ops(10),
                               "idle_gaps": run.trace.idle_by_label(10)}
    listed = cell.per_layer if run.trace is not None else cell.end_to_end
    for m in listed:
        value = spec.metric_reader(m["name"], cell.data)(run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            print(f"run.py: MISSING METRIC {m['name']}: its reader found "
                  "nothing to read in this run", file=sys.stderr, flush=True)

    failed = [r for r in run.due if r.status in ("poisoned", "quarantined")
              or any(t < 0 for t in r.tokens)]
    result["attempted"] = sum(w0 <= r.t_due < w1 for r in run.due)
    result["failed"] = sum(w0 <= r.t_due < w1 for r in failed)
    run.picked = check.sample([r for r in run.due if r.t_done is not None
                               and w0 <= r.t_done <= w1], run.seed)
    t_chk = time.perf_counter()
    gaps = check.gaps(cell.config, run.cfg, run.seed, run.picked,
                      **check.shapes(cell.mix))
    n_tok = sum(len(x) for x in gaps)
    worst = max((float(x.max()) for x in gaps if len(x)),
                default=float("inf"))
    lim = cell.limits
    result["correct"] = (worst <= lim["max_logit_gap"] and not failed
                         and n_tok >= lim["checked_tokens"])
    log(f"check: {len(run.picked)} requests, {n_tok} served tokens against "
        f"the reference in {time.perf_counter() - t_chk:.3f} s")
    result["checks"] = {
        "max_logit_gap": {"value": worst, "max": lim["max_logit_gap"]},
        "failed_requests": {"value": len(failed), "max": 0},
        "checked_tokens": {"value": n_tok, "min": lim["checked_tokens"]},
    }
    return result


def _log_traffic(run, loop) -> None:
    w0, w1 = run.window
    due = [r for r in run.due if w0 <= r.t_due < w1]
    late = [r.t_submit - r.t_due for r in run.due if r.rid >= 0
            and w0 <= r.t_due < w1]
    done = [r for r in due if r.t_done is not None and r.t_done <= w1]
    log(f"requests ({loop} loop): due in window {len(due)}, done "
        f"{len(done)}, failed "
        f"{sum(r.status == 'poisoned' for r in due)}, in flight at close "
        f"{sum(r.t_done is None for r in run.due if r.rid >= 0)}, not yet "
        f"submitted {sum(r.rid < 0 for r in run.due)}")
    if late:
        log(f"generator lateness: mean {sum(late) / len(late) * 1e3:.3f} ms, "
            f"max {max(late) * 1e3:.3f} ms")
    steps = [s for s in run.steps if w0 <= s.t0 < w1]
    if steps:
        dec = sum(len(s.decode) for s in steps) / len(steps)
        pre = sum(len(s.prefill) for s in steps) / len(steps)
        log(f"slot occupancy over {len(steps)} steps: {dec:.3f} decoding "
            f"and {pre:.3f} prefilling lanes of {run.slots} per step")


def emit(result: dict) -> None:
    for name, c in result["checks"].items():
        bound = (f"<= {c['max']}" if "max" in c else f">= {c['min']}")
        print(f"check {name}: {c['value']} {bound}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def setup(workload: str):
    """Put the harness (as a package, from the checkout's root) and the
    program (from src/) on the path, keep JAX's compile cache inside the
    checkout, and refuse to go on without the chips the cell asks for.
    -> the cell"""
    here = str(pathlib.Path(__file__).resolve().parent)
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if p not in (here, str(ROOT), str(ROOT / "src"))]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmarks.chip import spec
    try:
        cell = spec.load_cell(workload)
    except (FileNotFoundError, KeyError, ValueError) as e:
        fail(str(e))
    chips = next(w["chips"] for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]
        if w["name"] == workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX found no TPU (device 0 is {devs[0].platform}); the "
             "benchmark runs on the chip only")
    if len(devs) < chips:
        fail(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.launch.serve import enable_compile_cache
    enable_compile_cache()
    return cell


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = setup(args.workload)
    emit(judge(serve(cell, args.seed, args.seconds, bool(args.trace))))


if __name__ == "__main__":
    main()
