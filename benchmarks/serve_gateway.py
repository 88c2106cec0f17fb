"""Gateway serving benchmark — mixed-length multi-tenant traffic.

Reports throughput (tok/s), per-token latency percentiles (p50/p95) and
preemption/occupancy counters for the continuous-batching gateway over the
sealed paged KV pool, at the paper protection levels:

    off      — plain pool, no handshake sealing (paper's "VTA" row)
    trusted  — per-tenant CTR + per-page MAC + freshness ("VTA-trusted")

Two scenarios per mode:

    steady     all requests share one priority class (no preemption)
    preempt    a burst of high-priority interactive requests lands while
               low-priority batch requests hold every slot — the scheduler
               swaps sealed KV through the SealedStore host tier and back

A third section runs a *bursty admission* scenario (every request arrives
at once) in trusted mode and compares the decode write-back disciplines:

    whole-page   legacy baseline — the tail KV page re-seals entirely under
                 a bumped nonce on every decode token (O(page bytes)/token)
    open-page    slice-sealed open pages — only the new token slot is
                 sealed, pages close once when full (O(bytes written)/token,
                 the paper's §3.4 cost model)

at several prefill chunk sizes, reporting TTFT, prefill-chunk occupancy and
sealed-bytes-per-decode-token against the whole-page baseline.

A fourth section runs the *shared prefix* scenario (trusted mode): every
request opens with the same system-prompt prefix, comparing full prefill
(unshared) against the sealed prefix cache cold (publish cost in-window)
and warm (steady-state read-only page sharing) — TTFT, sealed pool pages
allocated per request and prefix hit rate.

Smoke-sized model so the numbers measure the *protocol machinery* (seal /
unseal / MAC per page, variable-occupancy gather, verbatim swap copies)
rather than raw FLOPs.

Artifacts (written to the working directory, see docs/OBSERVABILITY.md):

    BENCH_serve_gateway.json   every table row + full metric snapshots
    BENCH_trace.json           Chrome trace_event object from the traced
                               trusted/preempt cell — loads in Perfetto
    BENCH_audit.jsonl          that cell's hash-chained audit log + trailer
    BENCH_audit.key            the derived verification key (hex) for
                               tools/verify_audit.py
    BENCH_metrics.prom         that cell's Prometheus exposition — feed it
                               to tools/obs_dash.py with the audit JSONL
    BENCH_profile.json         per-phase cost attribution + dispatches per
                               step + predicted-vs-measured drift table
                               (gateway.profile_report()) — bench-gated by
                               tools/bench_diff.py on the deterministic
                               columns

The committed repo-root BENCH_serve_gateway.json / BENCH_micro.json are the
CI perf baselines: the bench-gate job re-runs ``run.py --smoke`` and diffs
the fresh artifacts against them with tools/bench_diff.py.
"""
from __future__ import annotations

import json
import time

import numpy as np


def _jsonable(o):
    """json.dump default: numpy scalars -> python numbers."""
    if hasattr(o, "item"):
        return o.item()
    raise TypeError(f"not JSON serializable: {type(o).__name__}")


def _submit_steady(gw, vocab, tenants, requests, max_new, seed):
    rng = np.random.RandomState(seed)
    for i in range(requests):
        plen = int(rng.randint(4, 17))
        gw.submit(f"tenant-{i % tenants}",
                  rng.randint(0, vocab, plen), max_new=max_new)


def _submit_preempt(gw, vocab, tenants, requests, max_new, seed):
    """Low-priority batch first (fills all slots), then a high-pri burst."""
    rng = np.random.RandomState(seed)
    n_hi = max(1, requests // 3)
    for i in range(requests - n_hi):
        plen = int(rng.randint(8, 17))
        gw.submit(f"batch-{i % tenants}", rng.randint(0, vocab, plen),
                  max_new=max_new, priority=0)
    gw.step()                              # batch traffic occupies the slots
    for i in range(n_hi):
        plen = int(rng.randint(4, 9))
        gw.submit(f"live-{i % tenants}", rng.randint(0, vocab, plen),
                  max_new=max_new, priority=5)


def _submit_burst(gw, vocab, tenants, requests, max_new, seed):
    """Bursty admission: every request arrives before the first step."""
    rng = np.random.RandomState(seed)
    for i in range(requests):
        plen = int(rng.randint(8, 25))
        gw.submit(f"tenant-{i % tenants}", rng.randint(0, vocab, plen),
                  max_new=max_new)


def run(arch: str = "granite-3-2b", tenants: int = 3, requests: int = 6,
        max_new: int = 8, slots: int = 4, burst: bool = True,
        burst_chunks: tuple = (0, 8), prefix: bool = True,
        out_dir: str = ".") -> dict:
    import jax

    from repro import configs
    from repro.models import registry
    from repro.serve import SecureGateway

    cfg = configs.get_config(arch, smoke=True)
    params = registry.get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    print(f"serve_gateway: {arch} (smoke), {tenants} tenants, "
          f"{requests} mixed-length requests, {max_new} new tokens each")
    header = (f"{'mode':>8} | {'scenario':>8} | {'tok/s':>8} | {'p50 ms':>8} "
              f"| {'p95 ms':>8} | {'ttft ms':>8} | {'pre-ttft':>8} | "
              f"{'swaps':>7} | {'occ %':>6} | {'pages':>5}")
    print(header)
    print("-" * len(header))
    result = {"benchmark": "serve_gateway", "arch": arch,
              "unix_time": time.time(),
              "params": {"tenants": tenants, "requests": requests,
                         "max_new": max_new, "slots": slots},
              "grid": [], "burst": [], "audit": None, "artifacts": {}}
    scenarios = (("steady", _submit_steady, dict(n_pages=64)),
                 ("preempt", _submit_preempt, dict(n_pages=64, slots=2)))
    for mode in ("off", "trusted"):
        for name, submit, knobs in scenarios:
            # the trusted/preempt cell is the observability showcase: it
            # records the trace and its audit log becomes the BENCH artifact
            traced = mode == "trusted" and name == "preempt"
            gw = SecureGateway(cfg, params, security=mode,
                               max_slots=knobs.get("slots", slots),
                               page_size=8, n_pages=knobs["n_pages"],
                               max_pages_per_seq=4, trace=traced)
            # warm-up pass compiles the graphs; re-run fresh traffic for timing
            submit(gw, cfg.vocab, tenants, requests, max_new, seed=0)
            gw.drain()
            gw.reset_metrics()
            if traced:
                gw.tracer.reset()       # trace the timed window only
            submit(gw, cfg.vocab, tenants, requests, max_new, seed=1)
            gw.drain()
            m = gw.metrics()
            result["grid"].append(
                {"mode": mode, "scenario": name, "metrics": m})
            if traced:
                result["audit"] = _export_obs(gw, result, out_dir)
            swaps = f"{m['swap_outs']}/{m['swap_ins']}"
            print(f"{mode:>8} | {name:>8} | {m['tok_per_s']:8.1f} | "
                  f"{m['p50_token_ms']:8.1f} | {m['p95_token_ms']:8.1f} | "
                  f"{m['mean_ttft_ms']:8.1f} | {m['preempted_ttft_ms']:8.1f} "
                  f"| {swaps:>7} | {m['pool_occupancy_pct']:6.1f} | "
                  f"{m['kv_pages_peak']:5d}")
    if burst:
        result["burst"] = run_burst(
            cfg, params, tenants=tenants, requests=requests,
            max_new=max_new, slots=slots, chunks=burst_chunks)
    if prefix:
        result["prefix"] = run_prefix(
            cfg, params, tenants=tenants, requests=requests,
            max_new=max_new, slots=slots)
    path = f"{out_dir}/BENCH_serve_gateway.json"
    with open(path, "w") as f:
        json.dump(result, f, indent=1, default=_jsonable)
    result["artifacts"]["results"] = path
    print(f"\nartifacts: {', '.join(sorted(result['artifacts'].values()))}")
    return result


def _export_obs(gw, result: dict, out_dir: str) -> dict:
    """Export the traced cell's trace + audit artifacts; -> audit summary."""
    trace_path = f"{out_dir}/BENCH_trace.json"
    audit_path = f"{out_dir}/BENCH_audit.jsonl"
    key_path = f"{out_dir}/BENCH_audit.key"
    prom_path = f"{out_dir}/BENCH_metrics.prom"
    profile_path = f"{out_dir}/BENCH_profile.json"
    n_events = gw.export_trace(trace_path, fmt="chrome")
    n_records = gw.export_audit(audit_path, key_path=key_path)
    with open(prom_path, "w") as f:
        f.write(gw.metrics_text())
    prof = gw.profile_report()
    with open(profile_path, "w") as f:
        json.dump(prof, f, indent=1, default=_jsonable)
    print(f"profile: {prof['dispatches_per_step']:.2f} dispatches/step "
          f"@ occupancy {prof['max_occupancy']} "
          f"({prof['dispatch_total']} total over {prof['steps']} steps)")
    for row in prof["phases"]:
        # no drift column off a chip with a cost model (the CPU among them)
        drift = (f"{row['ratio']:.1f}x" if row.get("ratio") is not None
                 else "-")
        print(f"  {row['phase']:<16} calls={row['calls']:<5} "
              f"sealed_B={row['sealed_bytes']:<9} "
              f"wall_us={row['wall_us']:<11.0f} drift={drift}")
    report = gw.verify_audit()
    if not report["ok"]:
        raise RuntimeError(f"audit chain failed verification: {report}")
    result["artifacts"].update(
        {"trace": trace_path, "audit": audit_path, "audit_key": key_path,
         "metrics_prom": prom_path, "profile": profile_path})
    summary = {"records": n_records, "trace_events": n_events,
               "kinds": gw.audit.kinds(), "verify": report}
    if gw.monitor is not None:
        summary["alerts"] = [a.to_dict() for a in gw.monitor.alerts]
    return summary


def run_burst(cfg, params, tenants: int = 3, requests: int = 6,
              max_new: int = 8, slots: int = 4,
              chunks: tuple = (0, 8)) -> list:
    """Bursty admission: whole-page-reseal baseline vs open pages, at
    several prefill chunk sizes (trusted mode, page_size 8).  Returns the
    rows (one dict per variant, with the full metric snapshot)."""
    from repro.serve import SecureGateway

    print()
    print(f"burst admission (trusted): {requests} requests at once, "
          "write-back discipline x prefill chunk size")
    header = (f"{'write-back':>12} | {'chunk':>5} | {'ttft ms':>8} | "
              f"{'chunk occ %':>11} | {'sealed B/tok':>12} | "
              f"{'vs baseline':>11} | {'closes':>6}")
    print(header)
    print("-" * len(header))
    variants = [("whole-page", False, 0)]
    variants += [("open-page", True, c) for c in chunks]
    baseline_bpt = None
    rows = []
    for name, open_pages, chunk in variants:
        gw = SecureGateway(cfg, params, security="trusted",
                           max_slots=slots, page_size=8, n_pages=64,
                           max_pages_per_seq=4, open_pages=open_pages,
                           prefill_chunk=chunk)
        _submit_burst(gw, cfg.vocab, tenants, requests, max_new, seed=0)
        gw.drain()
        gw.reset_metrics()
        _submit_burst(gw, cfg.vocab, tenants, requests, max_new, seed=1)
        gw.drain()
        m = gw.metrics()
        bpt = m["sealed_bytes_per_token"]
        if baseline_bpt is None:
            baseline_bpt = bpt
        ratio = baseline_bpt / bpt if bpt else float("inf")
        label = str(chunk) if chunk else "max"
        rows.append({"write_back": name, "prefill_chunk": chunk,
                     "vs_baseline": ratio if np.isfinite(ratio) else None,
                     "metrics": m})
        print(f"{name:>12} | {label:>5} | {m['mean_ttft_ms']:8.1f} | "
              f"{m['prefill_chunk_occupancy_pct']:11.1f} | {bpt:12.1f} | "
              f"{ratio:10.2f}x | {m['page_closes']:6d}")
    return rows


def run_prefix(cfg, params, tenants: int = 3, requests: int = 6,
               max_new: int = 8, slots: int = 4,
               prefix_len: int = 24) -> list:
    """Shared-prefix scenario (trusted): every request opens with the same
    ``prefix_len``-token system prompt plus a short private suffix.

        unshared      no prefix published — every request prefills the
                      whole prompt into freshly allocated sealed pages
        shared_cold   the prefix is published *inside* the timed window,
                      so its one-time prefill + seal + store publish cost
                      lands on this wave (first-deploy economics)
        shared_warm   steady state: published and warmed beforehand; every
                      request maps the sealed prefix pages read-only

    Reports mean TTFT, sealed pool pages allocated per request (the
    pages-saved story) and the window's prefix hit rate.  Runs at
    ``prefill_chunk=8`` (one page per chunk) so skipping cached pages
    skips whole prefill launches — with whole-prompt chunks the savings
    would be attention rows only and vanish into launch overhead at
    smoke sizes."""
    from repro.serve import SecureGateway

    print()
    print(f"shared prefix (trusted): {requests} requests, "
          f"{prefix_len}-token common prefix, {max_new} new tokens")
    header = (f"{'variant':>12} | {'ttft ms':>8} | {'pages/req':>9} | "
              f"{'hit rate':>8} | {'pages saved':>11} | {'cow':>4}")
    print(header)
    print("-" * len(header))
    prefix_tokens = np.random.RandomState(7).randint(
        0, cfg.vocab, prefix_len).astype(np.int32)

    def wave(gw, seed):
        rng = np.random.RandomState(seed)
        for i in range(requests):
            suffix = rng.randint(0, cfg.vocab, int(rng.randint(4, 9)))
            gw.submit(f"tenant-{i % tenants}",
                      np.concatenate([prefix_tokens,
                                      suffix.astype(np.int32)]),
                      max_new=max_new)
        gw.drain()

    rows = []
    for label in ("unshared", "shared_cold", "shared_warm"):
        gw = SecureGateway(cfg, params, security="trusted",
                           max_slots=slots, page_size=8, n_pages=64,
                           max_pages_per_seq=8, prefill_chunk=8)
        if label == "shared_warm":
            gw.register_prefix(prefix_tokens)
        wave(gw, seed=0)            # warm-up pass compiles the graphs
        gw.reset_metrics()
        allocs0 = gw.pool.stats["allocs"]
        if label == "shared_cold":
            gw.register_prefix(prefix_tokens)
        wave(gw, seed=1)
        m = gw.metrics()
        pages_per_req = (gw.pool.stats["allocs"] - allocs0) / requests
        rows.append({"label": label, "mean_ttft_ms": m["mean_ttft_ms"],
                     "pages_per_request": pages_per_req,
                     "prefix_hit_rate": m["prefix_hit_rate"],
                     "metrics": m})
        print(f"{label:>12} | {m['mean_ttft_ms']:8.1f} | "
              f"{pages_per_req:9.2f} | {m['prefix_hit_rate']:8.2f} | "
              f"{m['prefix_pages_saved']:11d} | "
              f"{m['prefix_cow_breaks']:4d}")
    return rows


if __name__ == "__main__":
    run()
